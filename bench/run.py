#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell (``workloads``) names a configuration
(``bench/configs/<name>.json``: sizes, source, the driver that builds the
workload from the program's public entry points) and a traffic mix
(``bench/traffic/<name>.json``); each per-layer metric is read by
``bench/metrics/<name>.py``; peaks come from ``bench/peaks.json``.

A run: set-up (imports, backend start, the workload built from ``--seed``,
every compile or cache load, the first calls), then a window of
``--seconds`` that calls the program again and again, then, with the
program's state freed, the comparison with the plain reference that decides
``correct``. ``--trace 1`` records the window with the profiler and reports
the per-layer metrics instead of the end-to-end ones.

It stops with a non-zero exit and prints no result when JAX finds no TPU or
fewer chips than the cell asks for, when ``REPRO_KERNEL_BACKEND`` or
``REPRO_USE_KERNEL`` is set (they swap the compiled kernels or the
aggregation path), or when the program is not next to the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up counts from the process start

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
FORBIDDEN_ENV = ("REPRO_KERNEL_BACKEND", "REPRO_USE_KERNEL")
# a fixed path inside the checkout: the directory is part of a cached
# entry's key, so a path that moves would never hit
CACHE_DIR = ROOT / ".bench_cache" / "jax"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}


class Refused(SystemExit):
    """A run that must not print a result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_json(path: Path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise Refused(f"missing {path.relative_to(ROOT)}")


def load_module(path: Path):
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_seed(seed: int) -> int:
    """The seed handed to the program: ``--seed`` may exceed what a 32-bit
    PRNG key holds, so it is hashed into [0, 2^30)."""
    digest = hashlib.sha256(str(seed).encode()).digest()
    return int.from_bytes(digest[:4], "little") % (1 << 30)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic, driver
    and metrics."""

    def __init__(self, name: str, chips: int, config: dict, traffic: dict,
                 end_to_end: list, per_layer: list,
                 bench_dir: Path = BENCH):
        self.name, self.chips = name, chips
        self.config, self.traffic = config, traffic
        self.driver = load_module(
            bench_dir / "drivers" / f"{config['driver']}.py")
        self.end_to_end, self.per_layer = end_to_end, per_layer
        self.bench_dir = bench_dir

    @classmethod
    def from_manifest(cls, manifest: dict, name: str) -> "Cell":
        """Resolve a cell of ``BENCHMARK.json`` to its files by name."""
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise Refused(f"no workload {name!r} in BENCHMARK.json; "
                          f"have {sorted(cells)}")
        entry = cells[name]
        configs = {c["name"]: c for c in manifest["configs"]}

        def reports(metric):
            return name in metric.get("workloads", [name])

        return cls(name, entry["chips"],
                   load_json(ROOT / configs[entry["config"]]["file"]),
                   load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
                   [m for m in manifest["end_to_end"] if reports(m)],
                   [m for m in manifest["per_layer"] if reports(m)])


class Listener:
    """Backend compile seconds and persistent-cache hits and misses."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event in CACHE_EVENTS:
            self.cache[CACHE_EVENTS[event]] += 1


def enable_cache(jax) -> None:
    """Every compile of the cell goes to the cache, however small or
    quick, so that a second run compiles nothing."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(jax, chips: int, require_tpu: bool = True) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise Refused(f"no TPU: JAX found {d0.platform}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips}


def memory_peak(jax, chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class Run:
    """What a per-layer metric reader sees of a traced run."""

    def __init__(self, workload, summary, units, window_s, peak):
        self.workload = workload
        self.trace = summary
        self.units = units
        self.window_s = window_s
        self.peak = peak


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True) -> dict:
    """One run of ``cell``; returns the result object (without printing)."""
    set_vars = [v for v in FORBIDDEN_ENV if os.environ.get(v)]
    if set_vars:
        raise Refused(f"{set_vars} set; unset them to run the chip path")
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused("the program (src/repro) is not in this checkout")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from bench import trace as tracing

    device = device_info(jax, cell.chips, require_tpu)
    if require_tpu:
        enable_cache(jax)
    listen = Listener(jax)
    peaks = load_json(cell.bench_dir / "peaks.json")["devices"]
    if require_tpu and device["kind"] not in peaks:
        raise Refused(f"no peaks for device kind {device['kind']!r} in "
                      f"bench/peaks.json")
    t_imported = time.perf_counter()

    wl = cell.driver.Workload(cell.config, cell.traffic, program_seed(seed))
    wl.prepare()
    setup_s = time.perf_counter() - T_START
    setup_compiles, setup_compile_s = listen.compiles, listen.compile_s
    print(f"setup_s={setup_s!r} imports_and_backend_s="
          f"{t_imported - T_START!r} build_and_first_steps_s="
          f"{setup_s - (t_imported - T_START)!r} compile_s={setup_compile_s!r}"
          f" compiles={setup_compiles} cache_hits={listen.cache['hits']} "
          f"cache_misses={listen.cache['misses']}", file=sys.stderr,
          flush=True)

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    units, ends = 0, []
    if trace:
        jax.profiler.start_trace(log_dir)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            t0 = time.perf_counter()
            while True:
                units += wl.step()
                ends.append(time.perf_counter())
                if ends[-1] - t0 >= seconds:
                    break
            wl.finish()
            window_s = time.perf_counter() - t0
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_compiles = listen.compiles - setup_compiles
    peak = memory_peak(jax, cell.chips)
    device["memory_peak_bytes"] = peak
    calls = len(ends)
    step_ms = sorted(1e3 * (b - a) for a, b in zip([t0] + ends, ends))
    print(f"window_s={window_s!r} calls={calls} units={units} "
          f"compiles_in_window={window_compiles} step_ms_min="
          f"{step_ms[0]!r} step_ms_median={step_ms[calls // 2]!r} "
          f"step_ms_max={step_ms[-1]!r}", file=sys.stderr, flush=True)

    summary = None
    if trace:
        summary = tracing.reduce(tracing.load_xplane(log_dir), cell.chips)
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    metrics = {}
    if trace:
        run = Run(wl, summary, units, window_s,
                  peaks.get(device["kind"], {}))
        for m in cell.per_layer:
            reader = load_module(cell.bench_dir / "metrics" / f"{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        rates = {cell.driver.UNIT: units / window_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": rates[m["name"]], "unit": m["unit"]}

    wl.release()
    readings = wl.check()
    limits = cell.config["limits"]
    # a reading that is not a number (NaN or infinite) is printed as null
    checks = {k: {"value": v if math.isfinite(v) else None,
                  "limit": limits[k]} for k, v in readings.items()}
    correct = (wl.failed == 0
               and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in checks.values()))
    out = {"correct": correct, "attempted": calls, "failed": wl.failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = tracing.breakdown(summary)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    cell = Cell.from_manifest(load_json(ROOT / "BENCHMARK.json"),
                              args.workload)
    out = measure(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
