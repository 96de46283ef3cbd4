#!/usr/bin/env python3
"""Proof that the federated sweep engine runs on a TPU, through the entry
points its users call.

    python chip_smoke.py              # one chip: phases 1-5
    python chip_smoke.py --chips 4    # the four-chip paths against mesh=None

One chip:

1. device: the platform is ``tpu`` and both kernel backends resolve to the
   compiled Pallas kernels (no interpret or XLA stand-in);
2. the paper-protocol family sweep (fedpbc/fedavg/fedavg_all/
   fedavg_known_p, bernoulli_tv, m=100, the 32x64x10 MLP, 2 lrs x 2 seeds,
   50 rounds) with the XLA aggregation and with the fused kernel, and the
   XLA arm once more on the host CPU;
3. cross-device scale: m=10,000 clients, cohorts of 256, synchronous and
   buffered aggregation, 20 rounds;
4. the full-width smollm-135m trainer (``repro.launch.train --full``,
   30 layers, bf16) for 4 rounds, and one client gradient through the flash
   kernel against the XLA attention;
5. a two-rung successive-halving search, whose segment carries are donated
   on the chip.

Four chips (``--chips 4``): phase 2 over the 4-chip ``("batch",)`` mesh,
and a small LM family sweep on the 2x2 ``("batch", "model")`` mesh, each
against the same run with ``mesh=None``.

Each phase prints its wall time, compile seconds and the device's peak
memory. A failed check or an exception fails its phase; the later phases
still run, so one run on the chip reports every fault, and the script then
exits non-zero without a result line. Without a TPU it stops at phase 1.
On success the last line of standard output is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import time
import traceback
from pathlib import Path
from unittest import mock

import numpy as np

ROOT = Path(__file__).resolve().parent
FORBIDDEN_ENV = ("REPRO_KERNEL_BACKEND", "REPRO_USE_KERNEL")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# Phase-2 bounds, each with its reason:
# - round-1 server params: both arms run the same local training; they
#   differ only in the order the aggregation adds up 100 float32 client
#   updates, which moves a result by a few ulps (~1e-7 at |x| ~ 1).
ROUND1_TOL = 1e-6
# - final test accuracy: a 1e-7 start drifts through 50 rounds of SGD on a
#   non-convex MLP, which can flip test points near the decision boundary;
#   0.02 is 20 of the 1000 test points and leaves the curve itself intact.
ACC_BAND = 0.02
# - round-1 server params, TPU ("highest" matmul precision) vs host CPU:
#   two backends. The TPU's float32 "highest" matmul is a multi-pass bf16
#   product and its exp/log differ from the CPU's by float32 ulps; five
#   local SGD steps on 32-sample batches carry that to 5.8e-6 (measured on
#   v5e). 2e-5 is 3x that, and 100x under the 1.9e-3 gap that
#   default-precision matmuls leave, which a precision bug would show.
CROSS_BACKEND_TOL = 2e-5
# Phase-4 bound on one client's gradient, flash kernel vs XLA attention,
# relative L2 over the whole gradient, both at "highest" matmul precision
# so that the comparison sees the kernel path and not bf16-pass matmul
# rounding: the model is bf16 (8 significant bits, eps 3.9e-3); the two
# paths round attention outputs and cotangents to bf16 at different
# points, and 30 layers add up such differences.
GRAD_REL_L2 = 2e-2


FAILURES = []


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str):
    if not ok:
        print(f"   FAILED: {msg}", flush=True)
        FAILURES.append(msg)


class Phases:
    """Per-phase wall time, backend compile seconds and peak memory."""

    def __init__(self, jax):
        self.jax = jax
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compile_s += duration

    @contextlib.contextmanager
    def __call__(self, name: str):
        print(f"== {name}", flush=True)
        t0, c0 = time.perf_counter(), self.compile_s
        try:
            yield
        except Exception as e:   # reported, and the run exits non-zero
            traceback.print_exc()
            print(f"   FAILED: {type(e).__name__}: {e}", flush=True)
            FAILURES.append(f"{name}: {type(e).__name__}")
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.jax.local_devices()]
        print(f"-- {name}: wall_s={time.perf_counter() - t0:.3f} "
              f"compile_s={self.compile_s - c0:.3f} "
              f"peak_bytes_in_use={peaks}", flush=True)


def max_abs_diff(a, b) -> float:
    import jax

    return max((float(np.max(np.abs(np.asarray(x, np.float64)
                                    - np.asarray(y, np.float64))))
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))),
               default=0.0)


def assert_close(a, b, tol: float, what: str):
    d = max_abs_diff(a, b)
    print(f"   {what}: max_abs_diff={d!r} (bound {tol!r})", flush=True)
    check(d <= tol, f"{what}: max_abs_diff {d} > {tol}")


# ---------------------------------------------------------------------------
# phase 2: the paper-protocol family sweep
# ---------------------------------------------------------------------------


def family_spec(**kw):
    from repro.core.algorithms import algo_family
    from repro.experiments import SweepSpec

    return SweepSpec(algorithms=algo_family("fedavg"),
                     schemes=("bernoulli_tv",), seeds=(0, 1), lrs=(0.05, 0.1),
                     num_clients=100, local_steps=5, batch_size=32, dim=32,
                     hidden=64, classes=10, rounds=50, eval_every=25, **kw)


def family_batch(spec, place=None):
    """The family's cell batch, on the default device, on ``place`` (a
    device), or split over ``place`` (a ``("batch",)`` mesh)."""
    import jax

    from repro.experiments.grid import get_traced_task, make_cell_batch
    from repro.experiments.shard import shard_batch

    fed = spec.cell_config(spec.algorithms[0], spec.schemes[0])
    batch = make_cell_batch(spec, fed, get_traced_task(spec),
                            algos=spec.algorithms)
    if place is None:
        return batch
    if isinstance(place, jax.sharding.Mesh):
        return shard_batch(batch, place)
    return jax.device_put(batch, place)


def round1_server(spec, place=None):
    """Server params after one round, per trajectory, from the resumable
    segment runner (one round per segment); ``place`` as in
    ``family_batch``."""
    from repro.experiments.grid import segment_runner_for

    run = segment_runner_for(spec, spec.algorithms[0], spec.schemes[0],
                             segment_rounds=1)
    batch = family_batch(spec, place)
    (states, _), _ = run.step(run.init(batch), batch)
    return states.server


def sweep_program_text(spec) -> str:
    """HLO of the scan stage ``run_sweep`` ran for ``spec``."""
    from repro.experiments.grid import _runner_for, get_traced_task

    fed = spec.cell_config(spec.algorithms[0], spec.schemes[0])
    runner = _runner_for(spec, fed, get_traced_task(spec),
                         ("loss", "num_active"))
    batch = family_batch(spec)
    st, ds = runner.init(batch)
    return runner.scan_batch.lower(
        st, ds, batch.keys["data"], batch.p_base, batch.hparams,
        batch.shared, batch.algo_id).compile().as_text()


def final_acc(cells):
    return np.stack([c.test_acc[:, -1] for c in cells])


def compare_arms(name, cells_a, cells_b, r1_a, r1_b, r1_tol=ROUND1_TOL):
    assert_close(r1_a, r1_b, r1_tol, f"{name}: round-1 server params")
    acc_a, acc_b = final_acc(cells_a), final_acc(cells_b)
    band = float(np.max(np.abs(acc_a - acc_b)))
    print(f"   {name}: final test acc max |diff|={band!r} (band {ACC_BAND})"
          f", mean acc {float(acc_a.mean())!r} vs {float(acc_b.mean())!r}",
          flush=True)
    check(band <= ACC_BAND, f"{name}: final accuracy differs by {band}")


def phase_family_sweep(jax):
    from repro.experiments import run_sweep

    xla = family_spec(use_kernel=False)
    ker = family_spec(use_kernel=True)
    cells = {}
    for name, spec in (("xla", xla), ("kernel", ker)):
        t0 = time.perf_counter()
        cells[name] = run_sweep(spec)
        print(f"   run_sweep[{name}]: {len(cells[name])} rows, "
              f"{time.perf_counter() - t0:.3f}s incl. compile", flush=True)
    for c in cells["xla"] + cells["kernel"]:
        check(np.isfinite(c.loss).all(), f"non-finite loss in {c.algo}")
    text = {name: sweep_program_text(spec)
            for name, spec in (("xla", xla), ("kernel", ker))}
    has = {k: "tpu_custom_call" in t for k, t in text.items()}
    print(f"   tpu_custom_call in the sweep program: {has}", flush=True)
    check(has == {"xla": False, "kernel": True},
          "the kernel sweep program must hold tpu_custom_call, the XLA one not")
    r1 = {"xla": round1_server(xla), "kernel": round1_server(ker)}
    compare_arms("kernel vs xla (tpu)", cells["kernel"], cells["xla"],
                 r1["kernel"], r1["xla"])

    # The host CPU runs the XLA arm. On the TPU, float32 matmuls run at
    # bf16-pass precision by default, so the TPU side of this comparison
    # runs at "highest" precision; the default-precision gap is printed.
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        cells_cpu = run_sweep(xla, devices=[cpu])
        r1_cpu = round1_server(xla, cpu)
        cells_hi = run_sweep(xla)
        r1_hi = round1_server(xla)
    compare_arms("tpu (highest) vs cpu", cells_hi, cells_cpu, r1_hi, r1_cpu,
                 CROSS_BACKEND_TOL)
    print(f"   tpu (default precision) vs cpu: round-1 max_abs_diff="
          f"{max_abs_diff(r1['xla'], r1_cpu)!r}, final acc max |diff|="
          f"{float(np.max(np.abs(final_acc(cells['xla']) - final_acc(cells_cpu))))!r}"
          " (not bounded)", flush=True)


# ---------------------------------------------------------------------------
# phase 3: cross-device scale
# ---------------------------------------------------------------------------


def phase_scale():
    from repro.experiments import SweepSpec, run_sweep
    from repro.scale import SYNC, Strategy

    spec = SweepSpec(algorithms=("fedpbc",), schemes=("bernoulli_ti",),
                     seeds=(0,), num_clients=10_000, cohort_size=256,
                     rounds=20, eval_every=10,
                     strategies=(SYNC, Strategy("buffered", buffer_size=128,
                                                deadline_rounds=3)))
    cells = {c.strategy: c for c in run_sweep(spec)}
    for name, c in cells.items():
        check(np.isfinite(c.loss).all(), f"scale[{name}]: non-finite loss")
        print(f"   scale[{name}]: final loss {float(c.loss[0, -1])!r}, "
              f"test acc {float(c.test_acc[0, -1])!r}", flush=True)
    commits = int(cells["buffered"].commit.sum())
    print(f"   scale[buffered]: {commits} commits in 20 rounds", flush=True)
    check(commits >= 1, "the buffered arm never committed")


# ---------------------------------------------------------------------------
# phase 4: the full-width smollm-135m trainer
# ---------------------------------------------------------------------------


def phase_lm_trainer(jax):
    import jax.numpy as jnp

    import repro.kernels.dispatch as dispatch
    from repro.launch import train

    args = train.parse_args([
        "--arch", "smollm-135m", "--full", "--clients", "8", "--batch", "2",
        "--seq", "128", "--local-steps", "2", "--rounds", "4",
        "--log-every", "1"])
    tr = train.build(args)
    cfg = tr.cfg
    check((cfg.num_layers, cfg.d_model, cfg.vocab_size, cfg.dtype)
          == (30, 576, 49152, "bfloat16"), f"not the full config: {cfg}")
    # bfloat16 is the compute dtype; the federated state is float32 masters
    held = {str(x.dtype) for x in jax.tree.leaves(
        (tr.state.server, tr.state.clients))}
    check(held == {"float32"}, f"federated state is not float32: {held}")

    # one client's gradient: compiled flash kernel vs the XLA attention
    batches, _ = tr.source.sample(tr.ds_state, 0, jax.random.PRNGKey(7))
    batch = jax.tree.map(lambda x: x[0, 0], batches)
    params = tr.state.server

    def grad_rel_l2():
        """Relative L2 distance of one client's gradient, flash kernel vs
        XLA attention (fresh traces, so the current precision applies)."""
        g_kernel = jax.jit(jax.grad(lambda p, b: tr.loss(p, b)))(params, batch)
        with mock.patch.object(dispatch, "resolve_attention_backend",
                               lambda backend=None: "xla"):
            g_xla = jax.jit(jax.grad(lambda p, b: tr.loss(p, b)))(params,
                                                                    batch)
        num = sum(float(jnp.sum(jnp.square(a.astype(jnp.float32)
                                           - b.astype(jnp.float32))))
                  for a, b in zip(jax.tree.leaves(g_kernel),
                                  jax.tree.leaves(g_xla)))
        den = sum(float(jnp.sum(jnp.square(b.astype(jnp.float32))))
                  for b in jax.tree.leaves(g_xla))
        return math.sqrt(num / den), math.sqrt(den)

    t0 = time.perf_counter()
    rel_default, _ = grad_rel_l2()
    with jax.default_matmul_precision("highest"):
        rel, norm = grad_rel_l2()
    print(f"   client gradient, flash vs xla: rel_l2={rel!r} at highest "
          f"precision (bound {GRAD_REL_L2}), {rel_default!r} at default "
          f"precision (not bounded), |g|={norm!r}, "
          f"{time.perf_counter() - t0:.3f}s incl. 4 compiles", flush=True)
    check(math.isfinite(rel) and rel <= GRAD_REL_L2,
          f"flash gradient differs from the xla gradient: {rel}")

    t0 = time.perf_counter()
    compiled = tr.run_rounds.lower(tr.state, tr.ds_state, tr.data_key,
                                   1).compile()
    mem = compiled.memory_analysis()
    print(f"   train chunk compile: {time.perf_counter() - t0:.3f}s; "
          f"memory_analysis: argument={mem.argument_size_in_bytes} "
          f"temp={mem.temp_size_in_bytes} output={mem.output_size_in_bytes} "
          f"alias={mem.alias_size_in_bytes}", flush=True)
    check("tpu_custom_call" in compiled.as_text(),
          "the smollm-135m train step holds no tpu_custom_call")

    log = train.train(tr, args)
    losses = [float(x) for e in log for x in e["loss"]]
    print(f"   per-round loss: {losses!r}; ln(vocab)="
          f"{math.log(cfg.vocab_size)!r}", flush=True)
    check(len(losses) == 4 and all(map(math.isfinite, losses)),
          f"non-finite or missing losses {losses}")
    # random init: logits have std ~ sqrt(576) * 0.02 ~ 0.5, so the round-1
    # loss sits within a few tenths above ln(V)
    check(abs(losses[0] - math.log(cfg.vocab_size)) <= 0.5,
          f"round-1 loss {losses[0]} is not near ln(49152)")


# ---------------------------------------------------------------------------
# phase 5: successive-halving search
# ---------------------------------------------------------------------------


def phase_search(jax):
    from repro.experiments import SweepSpec
    from repro.experiments.grid import segment_runner_for
    from repro.experiments.search import SearchSpec, run_search

    base = SweepSpec(algorithms=("fedpbc",), schemes=("bernoulli_tv",),
                     seeds=(0, 1), num_clients=100, rounds=20, eval_every=10)
    search = SearchSpec(base=base, rung_rounds=10, eta=2, num_candidates=4,
                        space=(("lr", ("log", 0.01, 0.5)),))
    out = run_search(search, verbose=True)
    best = out.best
    print(f"   search: waves={out.waves} device_rounds="
          f"{out.total_device_rounds} best lr={best.point['lr']!r} "
          f"eval={best.last_eval!r} compile_entries={out.compile_entries}",
          flush=True)
    check(out.waves == 2, f"expected 2 rungs, ran {out.waves}")
    check(out.compile_entries == {"init": 1, "scan": 1},
          f"search recompiled: {out.compile_entries}")
    check(math.isfinite(best.last_eval) and best.last_eval > 0.1,
          f"best candidate no better than chance: {best.last_eval}")
    # the carry a segment consumes is donated on the chip
    run = segment_runner_for(base, "fedpbc", "bernoulli_tv", segment_rounds=10,
                             metric_keys=("loss", "num_active"))
    spec1 = dataclasses.replace(base, lrs=(0.1,))
    batch = family_batch(spec1)
    carry = run.init(batch)
    leaf = jax.tree.leaves(carry)[0]
    run.step(carry, batch)
    print(f"   segment carry donated: {leaf.is_deleted()}", flush=True)
    check(leaf.is_deleted(), "the segment carry was not donated")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def spans_all(tree, n: int) -> bool:
    import jax

    return all(len(x.sharding.device_set) == n for x in jax.tree.leaves(tree))


def phase_batch_mesh(jax):
    """Phase 2's sweep over the 4-chip ("batch",) mesh against mesh=None.

    The fused-kernel arm must be bitwise equal: only the placement changes
    and every reduction stays within one trajectory, in the kernel. The
    XLA arm is held to phase 2's kernel-vs-XLA bounds instead: on the chip
    XLA reduces the [B, m, n] client updates in an order that depends on
    the per-device B (16 vs 4), one ulp apart after a round (first 4-chip
    run on v5e: 50-round losses 2.6e-4 apart, final accuracies 1e-3)."""
    from repro.experiments import run_sweep
    from repro.experiments.grid import _runner_for, get_traced_task
    from repro.experiments.shard import resolve_batch_mesh, run_sharded

    n = len(jax.devices())
    mesh = resolve_batch_mesh()
    for use_kernel in (True, False):
        spec = family_spec(use_kernel=use_kernel)
        plain = run_sweep(spec, mesh=None)
        sharded = run_sweep(spec)          # mesh="auto": every visible chip
        check([(a.algo, a.hparams) for a in plain]
              == [(b.algo, b.hparams) for b in sharded], "row order")
        fields = ("test_acc", "train_acc", "loss", "num_active")
        diffs = {f: max(float(np.max(np.abs(getattr(a, f) - getattr(b, f))))
                        for a, b in zip(plain, sharded)) for f in fields}
        print(f"   use_kernel={use_kernel}: {n}-chip mesh vs mesh=None, "
              f"max |diff| {diffs}", flush=True)
        if use_kernel:
            check(all(d == 0.0 for d in diffs.values()),
                  f"the kernel arm is not bitwise on the {n}-chip mesh")
        else:
            compare_arms(f"xla arm, {n}-chip mesh vs mesh=None", sharded,
                         plain, round1_server(spec, mesh),
                         round1_server(spec))
        fed = spec.cell_config(spec.algorithms[0], spec.schemes[0])
        runner = _runner_for(spec, fed, get_traced_task(spec),
                             ("loss", "num_active"))
        states, out = run_sharded(runner, family_batch(spec), mesh)
        check(spans_all((states, out), n),
              "a sharded output leaf does not span every chip")
    print(f"   every output leaf spans all {n} chips", flush=True)


def phase_lm_2d(jax):
    """A small LM family sweep on the 2x2 ("batch", "model") mesh against
    mesh=None, at the README bounds (1e-6 state/evals, 1e-5 loss)."""
    from repro.core.algorithms import algo_family
    from repro.experiments import SweepSpec
    from repro.experiments.grid import (
        _runner_for,
        get_traced_task,
        make_cell_batch,
    )
    from repro.experiments.shard import run_sharded_2d
    from repro.launch.mesh import make_2d_mesh

    spec = SweepSpec(algorithms=algo_family("fedavg"),
                     schemes=("bernoulli_ti",), seeds=(0, 1), lrs=(0.05, 0.1),
                     rounds=4, eval_every=2, num_clients=8, local_steps=2,
                     batch_size=2, per_client=16, task="lm", lm_d_model=64,
                     lm_layers=2, lm_seq=128, classes=4, lm_n_seqs=128,
                     lm_n_test=32)
    mesh = make_2d_mesh(2, 2)
    task = get_traced_task(spec)
    fed = spec.cell_config(spec.algorithms[0], spec.schemes[0])
    keys = ("loss", "num_active")
    batch = make_cell_batch(spec, fed, task, algos=spec.algorithms)
    st_p, out_p = _runner_for(spec, fed, task, keys)(batch)
    runner2d = _runner_for(spec, fed, task, keys, shard_mesh=mesh)
    st_s, out_s = run_sharded_2d(runner2d, batch, mesh)
    assert_close(st_s.server, st_p.server, 1e-6, "lm 2x2: final server")
    assert_close(out_s["evals"], out_p["evals"], 1e-6, "lm 2x2: evals")
    assert_close(out_s["metrics"]["loss"], out_p["metrics"]["loss"], 1e-5,
                 "lm 2x2: loss")
    check(np.isfinite(np.asarray(out_p["metrics"]["loss"])).all(),
          "lm: non-finite loss")
    check(spans_all((st_s, out_s), 4),
          "a 2x2-mesh output leaf does not span every chip")
    print("   every output leaf spans all 4 chips", flush=True)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases 1-5 on one chip; 4: only the four-chip "
                    "mesh paths and their mesh=None references")
    args = ap.parse_args(argv)

    # no hidden fallback: these select an interpret/XLA stand-in for the
    # compiled kernels, or flip the aggregation path, behind the run's back
    set_vars = [v for v in FORBIDDEN_ENV if os.environ.get(v)]
    if set_vars:
        _fail(f"{set_vars} set; unset them to run the chip path")
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no src/repro next to {Path(__file__).name}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()

    import jax

    from repro.kernels.dispatch import (
        resolve_attention_backend,
        resolve_backend,
    )

    phases = Phases(jax)
    print("== 1 device", flush=True)
    devs = jax.devices()
    d0 = devs[0]
    print(f"   platform={d0.platform} device_kind={d0.device_kind!r} "
          f"count={len(devs)} compile_cache={cache}", flush=True)
    if d0.platform != "tpu":
        _fail(f"no TPU: JAX found {d0.platform}")
    if len(devs) < args.chips:
        _fail(f"--chips {args.chips} but {len(devs)} visible")
    backends = (resolve_backend(), resolve_attention_backend())
    print(f"   resolve_backend()={backends[0]} "
          f"resolve_attention_backend()={backends[1]}", flush=True)
    if backends != ("compiled", "compiled"):
        _fail(f"kernel backends resolve to {backends}")

    if args.chips == 1:
        with phases("2 family sweep"):
            phase_family_sweep(jax)
        with phases("3 cross-device scale"):
            phase_scale()
        with phases("4 smollm-135m trainer"):
            phase_lm_trainer(jax)
        with phases("5 successive-halving search"):
            phase_search(jax)
    else:
        with phases("4-chip batch mesh"):
            phase_batch_mesh(jax)
        with phases("2x2 LM mesh"):
            phase_lm_2d(jax)

    if FAILURES:
        _fail(f"{len(FAILURES)} failed check(s): {FAILURES}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
