"""Benchmark driver — one module per paper table/figure. Prints CSV.

  python -m benchmarks.run                    # default (CPU-budget) suite
  python -m benchmarks.run --list             # what can run, then exit
  python -m benchmarks.run --only fig3
  python -m benchmarks.run --only fig2,table1,sweep   # comma-separated list
  python -m benchmarks.run --rounds 400       # longer federated runs
"""
from __future__ import annotations

import argparse
import time

# suite name -> (one-line description, arms within the suite's BENCH output).
# --list prints this table so nobody greps the source for --only values.
SUITE_INFO = {
    "fig2": ("Eq.-3 FedAvg bias series vs simulation", ()),
    "fig3": ("quadratic counterexample convergence curves", ()),
    "table1": ("final test accuracy grid (algorithms x schemes)", ()),
    "table2": ("rounds-to-target-accuracy grid (writes the machine-readable "
               "baseline JSON benchmarks/asha.py consumes)", ()),
    "fig8": ("alpha/gamma/delta/sigma0 ablations on one traced axis", ()),
    "extensions": ("beyond-paper extensions (fedpbc_m momentum)", ()),
    "throughput": ("scanned round engine vs per-round dispatch", ()),
    "sweep": ("batched sweep engine vs sequential/per-value baselines",
              ("seed_axis", "hparam_ablation", "algo_axis",
               "device_scaling")),
    "roofline": ("arithmetic-intensity roofline of the model zoo", ()),
    "kernels": ("pallas kernels vs reference ops (fused batched aggregation "
                "+ TPU-target oracles)",
                ("batched_agg_B8_m32_n1024", "batched_agg_B8_m256_n1024",
                 "batched_agg_B64_m32_n1024", "batched_agg_B64_m256_n1024")),
    "scale": ("cross-device cohort + buffered aggregation vs client count",
              ("scale_m1000", "scale_m10000", "scale_m50000")),
    "lm_sweep": ("federated LM family sweep on the 2-D (batch, model) mesh "
                 "vs one device, roofline-gated",
                 ("lm_family", "cohort")),
    "asha": ("successive-halving search vs exhaustive grid (time-to-target "
             "on the resumable segment runner)", ("asha_vs_grid",)),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of "
                         f"{'|'.join(SUITE_INFO)} (e.g. --only fig2,table1)")
    ap.add_argument("--list", action="store_true",
                    help="print available suites (and their BENCH arms) and "
                         "exit")
    ap.add_argument("--rounds", type=int, default=250)
    args = ap.parse_args()

    if args.list:
        for name, (desc, arms) in SUITE_INFO.items():
            line = f"{name:12s} {desc}"
            if arms:
                line += f"  [arms: {', '.join(arms)}]"
            print(line)
        return

    from benchmarks import (
        asha,
        extensions,
        fig2_bias,
        fig3_quadratic,
        fig8_ablations,
        kernels_bench,
        lm_sweep,
        roofline,
        scale,
        sweep_throughput,
        table1_accuracy,
        table2_rounds_to_target,
        throughput,
    )

    suites = {
        "fig2": lambda: fig2_bias.run(),
        "fig3": lambda: fig3_quadratic.run(rounds=min(args.rounds * 2, 800)),
        "table1": lambda: table1_accuracy.run(rounds=args.rounds),
        "table2": lambda: table2_rounds_to_target.run(rounds=args.rounds),
        "fig8": lambda: fig8_ablations.run(rounds=max(args.rounds // 2, 100)),
        "extensions": lambda: extensions.run(rounds=args.rounds),
        "throughput": lambda: throughput.run(rounds=max(args.rounds, 200)),
        "sweep": lambda: sweep_throughput.run(rounds=max(args.rounds // 2, 100)),
        "roofline": lambda: roofline.run(),
        "kernels": lambda: kernels_bench.run(),
        "scale": lambda: scale.run(rounds=max(args.rounds // 8, 20)),
        "lm_sweep": lambda: lm_sweep.run(rounds=max(args.rounds // 25, 4)),
        "asha": lambda: asha.run(rounds=max(args.rounds // 4, 32)),
    }
    assert set(suites) == set(SUITE_INFO)
    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in suites]
        if unknown:
            ap.error(f"unknown suite(s) {','.join(unknown)}; "
                     f"available: {','.join(suites)}")
    else:
        names = list(suites)
    for name in names:
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        suites[name]()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
