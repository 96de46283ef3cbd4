#!/usr/bin/env python3
"""Readings that set and test a cell's limits (not part of a benchmark run).

    python3 bench/control.py --workload <name> --seeds 1,2,3 \
        [--controls high,bf16] [--faults frozen,half_batch,altered]

For each seed, in one process: the numbers ``correct`` compares, read from
the program's timed path as a run reads them (set-up and one window
call, no timing); the same numbers from the control, which is the
program at a lower matrix-product precision where the program has that
path (``jax.default_matmul_precision``: ``highest``, ``high``,
``default``), and otherwise the reference at that precision put in the
program's place (``bf16``, :mod:`bench.refs.precision`); and from the
program with one fault planted underneath (see :data:`FAULTS`). Each
reading is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402

PROGRAM_PRECISIONS = ("highest", "high", "default")


@contextlib.contextmanager
def frozen():
    """Each round returns its input state unchanged (its metrics stand)."""
    import repro.core.federated as fed
    import repro.experiments.sweep as sweep

    real = fed.make_round_step

    def make(round_fn, source):
        step = real(round_fn, source)

        def frozen_step(state, ds_state, data_key):
            out = step(state, ds_state, data_key)
            return (state, ds_state) + tuple(out[2:])

        return frozen_step

    with mock.patch.object(fed, "make_round_step", make), \
            mock.patch.object(sweep, "make_round_step", make):
        yield


@contextlib.contextmanager
def half_batch():
    """Local training sees the first half of each step's batch: the mean
    loss and the gradient are taken over the rest."""
    import jax

    import repro.core.federated as fed

    real = fed.local_steps

    def halve(x):
        return jax.lax.slice_in_dim(x, 0, x.shape[1] // 2, axis=1)

    def local(loss_fn, optimizer, params, opt_state, batches, s):
        return real(loss_fn, optimizer, params, opt_state,
                    jax.tree.map(halve, batches), s)

    with mock.patch.object(fed, "local_steps", local):
        yield


@contextlib.contextmanager
def altered():
    """The link process's answer for client 0 is flipped where it is
    produced."""
    import repro.core.connectivity as conn

    real = conn.bernoulli_process

    def process(p_base, cfg, **kw):
        link = real(p_base, cfg, **kw)

        def sample(state, t, key):
            active, p_t, state = link.sample(state, t, key)
            return active.at[0].set(~active[0]), p_t, state

        return conn.LinkProcess(link.init, sample, link.name)

    with mock.patch.object(conn, "bernoulli_process", process):
        yield


FAULTS = {"frozen": frozen, "half_batch": half_batch, "altered": altered}


def program_readings(cell, seed, precision=None, fault=None):
    """The compared numbers of a run's timed path at ``seed``."""
    config = dict(cell.config)
    if precision is not None:
        config["matmul_precision"] = precision
    wl = cell.driver.Workload(config, cell.traffic,
                              bench_run.program_seed(seed))
    import jax

    jax.clear_caches()      # retrace: a planted fault or precision applies
    with (FAULTS[fault]() if fault else contextlib.nullcontext()):
        wl.prepare()
        wl.step()           # one window call, whose output is compared
    wl.release()
    return wl.check()


def control_readings(cell, seed, mode):
    if mode in PROGRAM_PRECISIONS:
        return program_readings(cell, seed, precision=mode)
    return cell.driver.Workload(cell.config, cell.traffic,
                                bench_run.program_seed(seed)).control(mode)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--control-seeds", type=int, default=0,
                    help="how many of the seeds get the controls (0: all)")
    ap.add_argument("--fault-seeds", type=int, default=3,
                    help="how many of the seeds get each fault")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = bench_run.Cell.from_manifest(
        bench_run.load_json(ROOT / "BENCHMARK.json"), args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    bench_run.enable_cache(jax)
    bench_run.device_info(jax, cell.chips)

    def emit(kind, seed, readings):
        print(json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                          "readings": readings}), flush=True)

    n_control = args.control_seeds or len(seeds)
    for i, seed in enumerate(seeds):
        emit("program", seed, program_readings(cell, seed))
        for mode in filter(None, args.controls.split(",")):
            if i < n_control:
                emit(f"control:{mode}", seed,
                     control_readings(cell, seed, mode))
    for fault in filter(None, args.faults.split(",")):
        for seed in seeds[:args.fault_seeds]:
            emit(f"fault:{fault}", seed,
                 program_readings(cell, seed, fault=fault))
    return 0


if __name__ == "__main__":
    sys.exit(main())
