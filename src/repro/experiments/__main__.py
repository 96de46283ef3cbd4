"""``python -m repro.experiments`` — alias for the sweep CLI (avoids the
runpy double-import warning ``-m repro.experiments.sweep`` prints)."""
from repro.experiments.sweep import main

if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
