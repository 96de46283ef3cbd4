#!/usr/bin/env python3
"""Readings that set and test the language-model cells' limits (not part
of a benchmark run).

    python3 bench/lm_control.py --workload smollm-family --seeds 1,2,3 \
        [--controls bf16_master,bf16_mean] [--control-seeds 10]

In one process, for each seed: the numbers ``correct`` compares, read
from the program's timed path as a run reads them (set-up's call, then
the check's call and the reference), with the active count of every
compared round and the worst leaf of each algorithm; then, on the first
``--control-seeds`` seeds, the same numbers from each control
(:data:`bench.refs.smollm_135m.CONTROLS`): the reference at the parent
program's numerics put in the program's place. Each reading is one JSON
line on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402


def detail(wl, rows, refs):
    """Per trajectory: its active counts over the compared rounds, its
    loss gap by round, and its worst leaf's change gap."""
    from bench.drivers import sweep as base

    out = []
    n = wl.early_spec.rounds
    for (_, _, traj), p, r in zip(wl.trajectories_of(wl.last), rows, refs):
        gaps = base.leaf_gaps(p["server"], r["server"], r["init"])
        leaf = max(gaps, key=gaps.get) if gaps else None
        out.append({"algo": traj[0], "seed": traj[2],
                    "active": [int(a) for a in r["num_active"]],
                    "loss_gap": [float(abs(a - b) / abs(b)) for a, b in
                                 zip(p["loss"][:n], r["loss"])],
                    "worst_leaf": leaf,
                    "change_gap": gaps.get(leaf, 0.0)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", type=int, default=0,
                    help="how many of the seeds get the controls (0: all)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = bench_run.Cell.from_manifest(
        bench_run.load_json(ROOT / "BENCHMARK.json"), args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    bench_run.enable_cache(jax)
    bench_run.device_info(jax, cell.chips)
    controls = list(filter(None, args.controls.split(",")))
    for i, seed in enumerate(seeds):
        wl = cell.driver.Workload(cell.config, cell.traffic,
                                  bench_run.program_seed(seed))
        wl.prepare()
        wl.release()
        rows = wl.rows(wl.last)
        wl.sound = wl.reference(wl.last)
        line = {"workload": cell.name, "seed": seed,
                "readings": wl.compare(rows, wl.sound),
                "detail": detail(wl, rows, wl.sound)}
        if i < (args.control_seeds or len(seeds)):
            line["controls"] = {name: wl.control(name) for name in controls}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
