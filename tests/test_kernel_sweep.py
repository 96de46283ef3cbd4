"""The fused aggregation kernel on the sweep hot path.

Acceptance guarantees of the kernel-dispatch layer:

1. ``AlgorithmSpec.aggregate(..., use_kernel=True)`` equals the XLA switch
   path bitwise (fp32, CPU) for every fusable family member — static and
   traced ``algo_id``, including zero-active rounds.
2. A full batched family sweep with ``use_kernel=True`` is bit-for-bit
   equal per trajectory to the XLA-path sweep, on the single-device path
   and on a multi-device ``("batch",)`` mesh (CI runs this file under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
3. Enabling ``use_kernel`` adds ZERO extra jit cache entries: a whole
   4-algorithm family ``run_sweep`` still compiles exactly one (init, scan)
   pair — the fused program rides the same runner cache.
4. Non-fusable families (stateful rules) fall back to the switch path
   unchanged under ``use_kernel=True``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.algorithms import AlgorithmSpec, algo_family
from repro.experiments import SweepSpec, run_sweep
from repro.experiments.grid import (
    _runner_for,
    get_traced_task,
    make_cell_batch,
)
from repro.experiments.shard import resolve_batch_mesh, run_sharded

N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_device_count=8)")

SEEDS = (0, 1)
BASE = SweepSpec(seeds=SEEDS, num_clients=8, dim=16, hidden=16, classes=10,
                 n_per_class=60, n_train=480, per_client=24,
                 batch_size=4, local_steps=3, rounds=5, eval_every=2,
                 lrs=(0.05, 0.1))
KSPEC = dataclasses.replace(BASE, use_kernel=True)
METRIC_KEYS = ("loss", "num_active")
FAMILY = algo_family("fedavg")
SCHEME = "bernoulli_tv"    # time-varying p_t exercises the known-p weighting


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _agg_inputs(key, m=6, empty=False):
    x_star = {"w": jax.random.normal(key, (m, 5, 3)),
              "b": jax.random.normal(jax.random.fold_in(key, 1), (m, 3))}
    server = {"w": jax.random.normal(jax.random.fold_in(key, 2), (5, 3)),
              "b": jax.random.normal(jax.random.fold_in(key, 3), (3,))}
    clients = jax.tree.map(
        lambda s: jnp.broadcast_to(s, (m,) + s.shape), server)
    active = (jnp.zeros((m,), bool) if empty
              else jax.random.uniform(jax.random.fold_in(key, 4), (m,)) < 0.5)
    p_t = jax.random.uniform(jax.random.fold_in(key, 5), (m,),
                             minval=0.05, maxval=1.0)
    return x_star, server, clients, active, p_t


@pytest.mark.parametrize("empty", [False, True])
def test_fused_aggregate_matches_switch_static(empty):
    """Per-member static dispatch: the fused kernel's (algo_state, server,
    clients) triple equals the XLA branch — including the zero-active
    round, where both must preserve the server params exactly.

    With clients active the contract is a tolerance of 3e-7 (about 2.5
    float32 ulps at |x| ~ 1): both sides run eagerly, op by op, and the
    XLA of jax 0.9 sums the kernel's fused select body in another order
    than the engine's standalone reduce, one ulp apart (5.96e-8 observed).
    The jitted sweep programs stay bitwise (test_sweep_use_kernel_bit_for_bit)."""
    spec = AlgorithmSpec(FAMILY)
    key = jax.random.PRNGKey(3 + empty)
    x_star, server, clients, active, p_t = _agg_inputs(key, empty=empty)
    state = spec.init(server, active.shape[0])
    for aid in range(len(FAMILY)):
        want = spec.aggregate(aid, state, server, clients, x_star, active,
                              p_t, jnp.int32(0))
        got = spec.aggregate(aid, state, server, clients, x_star, active,
                             p_t, jnp.int32(0), use_kernel=True)
        if empty:
            _assert_trees_equal(got, want)
            continue
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=3e-7, atol=3e-7)


def test_fused_aggregate_matches_switch_traced_batched():
    """Traced per-trajectory ``algo_id`` under vmap — the sweep layout: the
    one-pass fused kernel equals the evaluate-every-branch switch bitwise
    for a batch mixing all four members (and a zero-active trajectory)."""
    spec = AlgorithmSpec(FAMILY)
    B = 5
    keys = jax.random.split(jax.random.PRNGKey(11), B)
    ins = [_agg_inputs(k, empty=(i == 2)) for i, k in enumerate(keys)]
    x_star, server, clients, active, p_t = jax.tree.map(
        lambda *xs: jnp.stack(xs), *ins)
    algo_id = jnp.asarray([0, 1, 2, 3, 1], jnp.int32)
    m = active.shape[1]
    state = spec.init(jax.tree.map(lambda s: s[0], server), m)
    states = jax.tree.map(lambda s: jnp.broadcast_to(s, (B,) + s.shape), state)

    def run(uk):
        return jax.jit(jax.vmap(
            lambda aid, st, sv, cl, xs, act, pt: spec.aggregate(
                aid, st, sv, cl, xs, act, pt, jnp.int32(0), use_kernel=uk)))(
            algo_id, states, server, clients, x_star, active, p_t)

    _assert_trees_equal(run(True), run(False))


def test_non_fusable_family_falls_back_to_switch():
    """use_kernel=True on a stateful (non-fusable) family is a no-op: the
    switch path runs and results are identical."""
    spec = AlgorithmSpec(("fedau",))
    assert not spec.fusable
    key = jax.random.PRNGKey(7)
    x_star, server, clients, active, p_t = _agg_inputs(key)
    state = spec.init(server, active.shape[0])
    want = spec.aggregate(0, state, server, clients, x_star, active, p_t,
                          jnp.int32(0))
    got = spec.aggregate(0, state, server, clients, x_star, active, p_t,
                         jnp.int32(0), use_kernel=True)
    _assert_trees_equal(got, want)


def _family_batch_and_runners(scheme=SCHEME):
    task = get_traced_task(BASE)
    fed = BASE.cell_config(FAMILY[0], scheme)
    batch = make_cell_batch(BASE, fed, task, algos=FAMILY)
    r_xla = _runner_for(BASE, fed, task, METRIC_KEYS)
    r_ker = _runner_for(KSPEC, KSPEC.cell_config(FAMILY[0], scheme), task,
                        METRIC_KEYS)
    assert r_xla is not r_ker      # distinct traced programs, both cached
    return batch, r_xla, r_ker


def test_sweep_use_kernel_bit_for_bit():
    """All 4 family members x 2 lrs x 2 seeds x 5 rounds through the fused
    kernel: every leaf of the final states, per-round metrics and in-scan
    evals equals the XLA-path program bitwise (the interpret/CPU row of the
    dispatch layer's tolerance contract)."""
    batch, r_xla, r_ker = _family_batch_and_runners()
    _assert_trees_equal(r_ker(batch), r_xla(batch))


@multi_device
def test_sweep_use_kernel_sharded_bit_for_bit():
    """The fused-kernel program shards over the ("batch",) mesh like the
    XLA one: per-trajectory results equal the single-device kernel path AND
    the sharded XLA path bitwise."""
    batch, r_xla, r_ker = _family_batch_and_runners()
    mesh = resolve_batch_mesh()
    got = run_sharded(r_ker, batch, mesh)
    _assert_trees_equal(got, r_ker(batch))
    _assert_trees_equal(got, run_sharded(r_xla, batch, mesh))


def test_use_kernel_zero_extra_jit_entries(tmp_path, compiles_once):
    """The CI compile counter: a full 4-algorithm family run_sweep with
    use_kernel=True compiles exactly ONE (init, scan) jit entry — the fused
    program batches the whole family, adding zero entries over the XLA
    path's count."""
    spec = dataclasses.replace(KSPEC, rounds=3, eval_every=3,
                               algorithms=FAMILY, schemes=("bernoulli_ti",))
    cells = run_sweep(spec, metric_keys=METRIC_KEYS)
    assert [(c.algo, c.hparams["lr"]) for c in cells] == [
        (a, lr) for a in FAMILY for lr in spec.lrs]
    fed = spec.cell_config(FAMILY[0], "bernoulli_ti")
    runner = _runner_for(spec, fed, get_traced_task(spec), METRIC_KEYS)
    compiles_once(runner.init_batch, runner.scan_batch)
    # the kernel path is live, not decorative: distinct algorithms diverge
    finals = {c.algo: c.test_acc.tobytes() for c in cells
              if c.hparams["lr"] == spec.lrs[0]}
    assert len(set(finals.values())) == len(FAMILY)
    # and equals the XLA-path sweep cell for cell
    xspec = dataclasses.replace(spec, use_kernel=False)
    for kc, xc in zip(cells, run_sweep(xspec, metric_keys=METRIC_KEYS)):
        assert (kc.algo, kc.hparams) == (xc.algo, xc.hparams)
        np.testing.assert_array_equal(kc.test_acc, xc.test_acc)
        np.testing.assert_array_equal(kc.loss, xc.loss)


def test_spec_use_kernel_defers_to_env(monkeypatch):
    """SweepSpec.use_kernel=None resolves through the dispatch env default;
    the resolved value keys the runner cache."""
    import repro.experiments.grid as grid_mod

    spec = dataclasses.replace(BASE, rounds=2, eval_every=0)
    task = get_traced_task(spec)
    fed = spec.cell_config("fedpbc", "bernoulli_ti")
    monkeypatch.delenv("REPRO_USE_KERNEL", raising=False)
    r_off = _runner_for(spec, fed, task, METRIC_KEYS)
    monkeypatch.setenv("REPRO_USE_KERNEL", "1")
    r_on = _runner_for(spec, fed, task, METRIC_KEYS)
    assert r_on is not r_off
    # explicit False pins the XLA path regardless of the env
    r_pinned = _runner_for(dataclasses.replace(spec, use_kernel=False), fed,
                           task, METRIC_KEYS)
    assert r_pinned is r_off
