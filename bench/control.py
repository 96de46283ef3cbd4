#!/usr/bin/env python3
"""Readings that set and test a cell's limits (not part of a benchmark run).

    python3 bench/control.py --workload <name> --seeds 1,2,3 \
        [--growth 1,3,10,25,50] [--controls high,bf16] \
        [--faults frozen,half_batch,altered]

In one process: for each seed, the numbers ``correct`` compares, read from
the program's timed path as a run reads them (set-up and one window
call, no timing), with the trajectory and leaf behind each worst change
gap, and with ``--growth`` the worst change gap of a call over each of
those round counts against the reference over as many; then the same
numbers from the control, which is the program at a lower matrix-product
precision where the program has that path
(``jax.default_matmul_precision``: ``highest``, ``high``, ``default``),
and otherwise the reference at that precision put in the program's place
(``bf16``, :mod:`bench.refs.precision`); then from the program with one
fault planted underneath (see :data:`FAULTS`). Each reading is one JSON
line on standard output.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
from pathlib import Path
from unittest import mock

import numpy as np

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


def program_readings(cell, seed, precision=None, fault=None, growth=()):
    """The compared numbers of a run's timed path at ``seed``; with
    ``growth``, what :func:`worst_gaps` says of the program's server
    after a call over each of those round counts. The program is traced
    as the last call left it: :func:`main` clears JAX's caches where the
    precision or the fault changes."""
    config = dict(cell.config)
    if precision is not None:
        config["matmul_precision"] = precision
    wl = cell.driver.Workload(config, cell.traffic,
                              bench_run.program_seed(seed))
    with (FAULTS[fault]() if fault else contextlib.nullcontext()):
        wl.prepare()
        wl.step()           # one window call, whose output is compared
        wl.release()
        rows = wl.rows(wl.last)
        calls = {r: wl.call_servers(dataclasses.replace(
            wl.spec, rounds=r, eval_every=r)) for r in growth}
    keep = sorted({*growth, wl.early_spec.rounds, wl.spec.rounds})
    refs = wl.reference(wl.last, keep=keep)
    readings = wl.compare(rows, refs)
    if fault or precision:
        return readings, {}
    trajs = [t for _, _, t in wl.trajectories_of(wl.last)]
    detail = {"worst": {
        name: worst_gaps(cell.driver, trajs, [p["servers"][r] for p in rows],
                         refs, r)
        for name, r in (("change_gap", wl.early_spec.rounds),
                        ("call_change_gap", wl.spec.rounds))}}
    detail["loss_gap_by_round"] = loss_gap_by_round(trajs, rows, refs)
    if growth:
        detail["growth"] = {r: dict(
            worst_gaps(cell.driver, trajs, servers, refs, r),
            repeats_window=bool(cell.driver.same_rounds(cells, wl.last, r)))
            for r, (cells, servers) in calls.items()}
    return readings, detail


def worst_gaps(driver, trajs, servers, refs, rounds):
    """The worst leaf gap (``driver.leaf_gaps``) of the program's
    ``servers`` against the reference's after ``rounds`` rounds: its
    trajectory and leaf, and the worst of each algorithm."""
    worst, by_algo = None, {}
    for traj, server, r in zip(trajs, servers, refs):
        for leaf, gap in driver.leaf_gaps(server, r["servers"][rounds],
                                          r["init"]).items():
            by_algo[traj.algo] = max(by_algo.get(traj.algo, 0.0), gap)
            if worst is None or gap > worst["gap"]:
                worst = {"gap": gap, "algo": traj.algo, "lr": traj.lr,
                         "seed": traj.seed, "leaf": leaf}
    return dict(worst or {"gap": 0.0}, by_algo=by_algo)


def loss_gap_by_round(trajs, rows, refs):
    """Each algorithm's worst relative loss gap in every round of the
    window call."""
    out = {}
    for traj, p, r in zip(trajs, rows, refs):
        gap = (np.abs(np.asarray(p["loss"], np.float64) - r["loss"])
               / np.abs(r["loss"]))
        out[traj.algo] = np.maximum(out.get(traj.algo, 0.0), gap)
    return {algo: [float(g) for g in gaps] for algo, gaps in out.items()}


def control_readings(cell, seed, mode):
    if mode in PROGRAM_PRECISIONS:
        return program_readings(cell, seed, precision=mode)[0]
    return cell.driver.Workload(cell.config, cell.traffic,
                                bench_run.program_seed(seed)).control(mode)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--growth", default="",
                    help="round counts at which to follow the server's "
                         "change gap on the seeds' sound readings")
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

    def emit(kind, seed, readings, **detail):
        print(json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                          "readings": readings, **detail}), flush=True)

    growth = [int(r) for r in filter(None, args.growth.split(","))]
    for seed in seeds:
        readings, detail = program_readings(cell, seed, growth=growth)
        emit("program", seed, readings, **detail)
    # each control and fault retraces the program once, for all its seeds
    for mode in filter(None, args.controls.split(",")):
        jax.clear_caches()
        for seed in seeds[:args.control_seeds or len(seeds)]:
            emit(f"control:{mode}", seed, control_readings(cell, seed, mode))
    for fault in filter(None, args.faults.split(",")):
        jax.clear_caches()
        for seed in seeds[:args.fault_seeds]:
            emit(f"fault:{fault}", seed,
                 program_readings(cell, seed, fault=fault)[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
