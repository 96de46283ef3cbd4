"""Cross-device scale subsystem (``repro.scale``).

Acceptance guarantees:

1. Cohort mode keeps per-round client tensors at O(C): ``FedState`` holds
   no ``[m, ...]`` client-parameter or optimizer leaf, and a cohort round
   at m in the tens of thousands compiles and runs on CPU.
2. ``source.sample_cohort`` over the full-population cohort
   ``arange(m)`` IS the dense ``source.sample`` — bit for bit — so the
   cohort path changes which clients train, never what they see.
3. Every stateful rule (fedau / mifa / f3ast / fedpbc_m) has a sparse
   cohort branch whose scatters touch cohort rows only.
4. The buffered strategy axis is one more traced batch dimension: a
   (SYNC, buffered) sweep compiles ONE (init, scan) program, and its
   store rows carry the strategy coordinate.
5. ``SweepSpec`` rejects malformed ``strategies`` / ``cohort_size`` axes
   at construction with the offending field named.
6. The buffer engine's commit policy matches its spec: ``wait_for_full``
   holds until the buffer fills; otherwise the deadline forces a commit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FederationConfig
from repro.core import init_fed_state, make_link_process, make_run_rounds
from repro.core.algorithms import make_algorithm_spec
from repro.data import classification_source, fixed_source
from repro.experiments import ResultsStore, SweepSpec, run_sweep
from repro.experiments.grid import _runner_for, get_traced_task
from repro.optim import sgd
from repro.scale import (
    BUFFER_METRIC_KEYS,
    SYNC,
    Strategy,
    buffered_aggregate,
    init_buffer_state,
    knobs_of,
    sample_cohort,
    strategy_knob_columns,
)
from repro.kernels.masked_agg import OP_MEAN

N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_device_count=8)")

BASE = SweepSpec(algorithms=("fedpbc",), seeds=(0, 1), num_clients=8, dim=16, hidden=16, classes=10,
                 n_per_class=60, n_train=480, per_client=24,
                 batch_size=4, local_steps=2, rounds=4, eval_every=2,
                 lrs=(0.1,))
BUFFERED = Strategy("buffered", buffer_size=4, deadline_rounds=3)
METRIC_KEYS = ("loss", "num_active") + BUFFER_METRIC_KEYS


def _quadratic_setup(m, C=None, *, algo="fedpbc", p=0.5, strategy=None,
                     scheme="bernoulli"):
    """A tiny quadratic federated problem on the real engine."""
    fed = FederationConfig(algorithm=algo, num_clients=m, local_steps=2,
                           scheme=scheme)
    spec = make_algorithm_spec((algo,), fed)
    link = make_link_process(jnp.full((m,), p), fed)
    loss = lambda params, batch: jnp.sum((params["x"] - batch["u"].sum()) ** 2)
    opt = sgd(0.05)
    source = fixed_source({"u": jnp.zeros((m, fed.local_steps, 1))})
    run = make_run_rounds(loss, opt, spec, link, fed, source,
                          metric_keys=("loss", "num_active", "staleness")
                          + (BUFFER_METRIC_KEYS if strategy is not None
                             or C is not None else ()),
                          donate=False, strategy=strategy, cohort_size=C)
    st = init_fed_state(jax.random.PRNGKey(0), {"x": jnp.ones(3)}, fed, spec,
                        link, opt, stateless_clients=C is not None,
                        buffered=strategy is not None
                        or (C is not None and spec.fusable))
    return run, st, source.init(jax.random.PRNGKey(2))


# ---------------------------------------------------------------------------
# 1. O(C) memory
# ---------------------------------------------------------------------------

def test_cohort_round_memory_is_o_of_c():
    """At m=50_000 the cohort engine must hold NO [m, n_params] tensor:
    client params/opt state are () and every FedState leaf is either O(m)
    scalars-per-client bookkeeping or O(n_params) server/buffer state."""
    m, C, n_params = 50_000, 256, 3
    run, st, ds = _quadratic_setup(m, C)
    assert st.clients == () and st.opt_state == ()
    for leaf in jax.tree.leaves(st):
        assert leaf.size <= max(m, 64 * n_params)   # never m x n_params
    st, ds, mets = run(st, ds, jax.random.PRNGKey(3), 2)
    assert st.clients == () and st.opt_state == ()
    assert np.isfinite(np.asarray(mets["loss"])).all()
    # the round saw C-sized cohorts, not the population
    assert float(np.asarray(mets["num_active"]).max()) <= C


def test_cohort_sampler_validates_and_is_unique():
    key = jax.random.PRNGKey(0)
    cohort = np.asarray(sample_cohort(key, 100, 32))
    assert cohort.shape == (32,) and len(set(cohort.tolist())) == 32
    assert cohort.min() >= 0 and cohort.max() < 100
    with pytest.raises(ValueError, match="cohort"):
        sample_cohort(key, 100, 0)
    with pytest.raises(ValueError, match="cohort"):
        sample_cohort(key, 100, 101)


# ---------------------------------------------------------------------------
# 2. cohort data == dense data on the full population
# ---------------------------------------------------------------------------

def test_sample_cohort_full_population_is_dense_sample():
    m, s, b, d = 6, 2, 3, 4
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(40, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, size=(40,)), jnp.int32)
    idx = jnp.asarray(rng.integers(0, 40, size=(m, 8)), jnp.int32)
    src = classification_source(x, y, idx, local_steps=s, batch_size=b)
    ds = src.init(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(7)
    dense, _ = src.sample(ds, 3, key)
    cohort, _ = src.sample_cohort(ds, 3, key, jnp.arange(m))
    for a, c in zip(jax.tree.leaves(dense), jax.tree.leaves(cohort)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ---------------------------------------------------------------------------
# 3. stateful rules: sparse cohort branches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["fedau", "mifa", "f3ast", "fedpbc_m"])
def test_stateful_cohort_engine_runs_and_touches_cohort_rows_only(algo):
    m, C = 64, 8
    run, st, ds = _quadratic_setup(m, C, algo=algo)
    st1, ds, mets = run(st, ds, jax.random.PRNGKey(3), 5)
    assert np.isfinite(np.asarray(mets["loss"])).all()
    assert np.isfinite(
        np.asarray(jax.tree.leaves(st1.server)[0], np.float64)).all()
    # rows never sampled into a cohort keep their initial state: with
    # 5 rounds x C=8 at most 40 of 64 rows were touched
    touched = np.asarray(st1.last_active) >= 0
    assert touched.sum() <= 5 * C
    if algo == "mifa":
        mem0 = np.asarray(jax.tree.leaves(st.algo_state.mem)[0])
        mem1 = np.asarray(jax.tree.leaves(st1.algo_state.mem)[0])
        unchanged = np.all((mem0 == mem1).reshape(m, -1), axis=-1)
        assert unchanged.sum() >= m - 5 * C


def test_buffered_cohort_round_reports_the_buffers_metrics():
    """Cohort draws into a buffer larger than three rounds of arrivals: no
    round commits, and the fill counts every arrival so far."""
    strat = Strategy("buffered", buffer_size=32, deadline_rounds=50)
    run, st, ds = _quadratic_setup(64, 8, strategy=strat)
    _, _, mets = run(st, ds, jax.random.PRNGKey(3), 3)
    assert np.asarray(mets["commit"]).tolist() == [0.0, 0.0, 0.0]
    fill = np.asarray(mets["buffer_fill"])
    np.testing.assert_array_equal(fill,
                                  np.cumsum(np.asarray(mets["num_active"])))


def test_buffered_strategy_refused_for_stateful_rules():
    m = 8
    fed = FederationConfig(algorithm="fedau", num_clients=m, local_steps=2)
    spec = make_algorithm_spec(("fedau",), fed)
    link = make_link_process(jnp.full((m,), 0.5), fed)
    with pytest.raises(ValueError, match="empty-state family"):
        make_run_rounds(lambda p, b: jnp.sum(p["x"] ** 2), sgd(0.1), spec,
                        link, fed, fixed_source({"u": jnp.zeros((m, 2, 1))}),
                        strategy=BUFFERED)


# ---------------------------------------------------------------------------
# 4. the strategy axis is one compiled program
# ---------------------------------------------------------------------------

def test_buffered_sweep_compiles_one_program_and_records_strategy(
        tmp_path, compiles_once):
    spec = dataclasses.replace(BASE, strategies=(SYNC, BUFFERED),
                               schemes=("bernoulli_ti",))
    store = ResultsStore(str(tmp_path / "sweeps"))
    cells = run_sweep(spec, store=store, suite="scale",
                      metric_keys=METRIC_KEYS)
    assert [c.strategy for c in cells] == ["sync", "buffered"]
    fed = spec.cell_config("fedpbc", "bernoulli_ti")
    runner = _runner_for(spec, fed, get_traced_task(spec), METRIC_KEYS)
    # both strategies (and any knob grid) share ONE (init, scan) pair —
    # the knobs are traced per-trajectory columns, not compile constants
    compiles_once(runner.init_batch, runner.scan_batch)
    rows = store.records(suite="scale")
    assert [r["strategy"] for r in rows] == ["sync", "buffered"]
    # buffered rows carry the commit trace; its cadence is a real policy
    # (neither no-commit nor the sync every-round commit)
    sync_c, buf_c = cells
    assert buf_c.commit is not None
    commits = np.asarray(buf_c.commit).sum(axis=1)
    assert (commits >= 1).all() and (commits < spec.rounds).all()
    assert (np.asarray(sync_c.commit).sum(axis=1) == spec.rounds).all()
    summ = buf_c.summary()
    assert "commits" in summ and "commit_staleness" in summ
    assert "participation" in summ


@multi_device
def test_buffered_sweep_sharded_matches_single_device():
    spec = dataclasses.replace(BASE, strategies=(SYNC, BUFFERED),
                               schemes=("bernoulli_ti",))
    ref = run_sweep(spec, metric_keys=METRIC_KEYS, devices=jax.devices()[:1])
    sh = run_sweep(spec, metric_keys=METRIC_KEYS)
    assert [c.strategy for c in sh] == [c.strategy for c in ref]
    for a, b in zip(sh, ref):
        np.testing.assert_array_equal(a.test_acc, b.test_acc)
        np.testing.assert_array_equal(a.loss, b.loss)
        np.testing.assert_array_equal(np.asarray(a.commit),
                                      np.asarray(b.commit))


def test_cohort_sweep_runs_at_scale_smoke():
    """The acceptance workload shape (large m, C=256 cohort, buffered
    strategy) as a fast smoke: one compiled program, finite results."""
    spec = dataclasses.replace(
        BASE, num_clients=10_000, cohort_size=64,
        strategies=(Strategy("buf", buffer_size=48, deadline_rounds=2),),
        schemes=("bernoulli_ti",), seeds=(0,), rounds=3, eval_every=3)
    cells = run_sweep(spec, metric_keys=METRIC_KEYS)
    (cell,) = cells
    assert cell.strategy == "buf"
    assert np.isfinite(cell.test_acc).all()
    assert float(np.asarray(cell.num_active).max()) <= 64
    # participation is measured against the cohort, not the population
    assert 0.0 <= cell.summary()["participation"]["mean"] <= 1.0


# ---------------------------------------------------------------------------
# 5. SweepSpec validation
# ---------------------------------------------------------------------------

def test_sweep_spec_strategy_axis_validation_names_offending_field():
    with pytest.raises(ValueError, match="SweepSpec.strategies is empty"):
        dataclasses.replace(BASE, strategies=())
    with pytest.raises(ValueError, match="SweepSpec.strategies entries"):
        dataclasses.replace(BASE, strategies=(SYNC, "buffered"))
    with pytest.raises(ValueError,
                       match="SweepSpec.strategies.*duplicate.*sync"):
        dataclasses.replace(BASE, strategies=(SYNC, Strategy("sync")))
    with pytest.raises(ValueError,
                       match=r"SweepSpec.strategies\['big'\].buffer_size"):
        dataclasses.replace(BASE, strategies=(
            Strategy("big", buffer_size=BASE.num_clients + 1),))
    with pytest.raises(ValueError,
                       match=r"SweepSpec.strategies\['big'\].buffer_size"):
        # with a cohort, the buffer can only ever see C arrivals per round
        dataclasses.replace(BASE, cohort_size=4,
                            strategies=(Strategy("big", buffer_size=6),))
    with pytest.raises(ValueError,
                       match=r"SweepSpec.strategies\['rush'\].deadline"):
        dataclasses.replace(BASE, strategies=(
            Strategy("rush", deadline_rounds=0),))
    with pytest.raises(ValueError,
                       match=r"SweepSpec.strategies\['hot'\].staleness"):
        dataclasses.replace(BASE, strategies=(
            Strategy("hot", staleness_discount=1.5),))
    with pytest.raises(ValueError, match="SweepSpec.cohort_size"):
        dataclasses.replace(BASE, cohort_size=0)
    with pytest.raises(ValueError, match="SweepSpec.cohort_size"):
        dataclasses.replace(BASE, cohort_size=BASE.num_clients + 1)
    with pytest.raises(ValueError, match="buffered entries"):
        dataclasses.replace(BASE, algorithms=("fedau",),
                            strategies=(SYNC, BUFFERED))
    # valid axes still construct
    dataclasses.replace(BASE, strategies=(SYNC, BUFFERED), cohort_size=4)


def test_knob_normalization_and_columns():
    assert knobs_of(None) == knobs_of(SYNC)
    assert SYNC.is_sync and not BUFFERED.is_sync
    assert Strategy("w", wait_for_full=True, buffer_size=1).is_sync is False
    with pytest.raises(ValueError, match="missing"):
        knobs_of({"buffer_size": 4})
    cols = strategy_knob_columns((SYNC, BUFFERED), block=3)
    assert set(cols) == {"wait_for_full", "buffer_size", "deadline_rounds",
                        "staleness_discount"}
    np.testing.assert_array_equal(np.asarray(cols["buffer_size"]),
                                  [1, 1, 1, 4, 4, 4])
    assert cols["wait_for_full"].dtype == jnp.bool_
    assert cols["staleness_discount"].dtype == jnp.float32


# ---------------------------------------------------------------------------
# 6. buffer engine commit policy
# ---------------------------------------------------------------------------

def _fold(buf, server, active, knobs):
    m = active.shape[0]
    x_star = {"x": jnp.ones((m, 2))}
    in_buffer = buf.in_buffer | active
    return buffered_aggregate(buf, server, x_star, active,
                              jnp.full((m,), 0.5), knobs, op=OP_MEAN,
                              m_total=m, in_buffer_new=in_buffer)


def test_wait_for_full_commits_only_when_full():
    m = 4
    server = {"x": jnp.zeros(2)}
    knobs = knobs_of(Strategy("w", wait_for_full=True, buffer_size=3,
                              deadline_rounds=1))
    buf = init_buffer_state(server, m)
    two = jnp.asarray([True, True, False, False])
    buf, srv, commit, mets = _fold(buf, server, two, knobs)
    assert not bool(commit)                      # 2 < 3: deadline ignored
    assert float(mets["buffer_fill"]) == 2.0
    np.testing.assert_array_equal(np.asarray(jax.tree.leaves(srv)[0]), 0.0)
    buf, srv, commit, mets = _fold(buf, server, two, knobs)
    assert bool(commit)                          # 4 >= 3: fills, commits
    assert float(buf.count) == 0 and not bool(buf.in_buffer.any())
    # committed mean of four all-ones contributions is exactly ones
    np.testing.assert_array_equal(np.asarray(srv["x"]), 1.0)
    # the first two contributions waited one round, the new two zero
    assert float(mets["commit_staleness"]) == pytest.approx(0.5)


def test_deadline_forces_commit_on_empty_rounds():
    m = 4
    server = {"x": jnp.zeros(2)}
    # buffer_size 4 never fills with one arrival per round; the deadline acts
    knobs = knobs_of(Strategy("d", buffer_size=4, deadline_rounds=2))
    buf = init_buffer_state(server, m)
    one = jnp.asarray([True, False, False, False])
    buf, _, commit, _ = _fold(buf, server, one, knobs)
    assert not bool(commit)                      # 1 < 4 and 1 < deadline 2
    buf, srv, commit, _ = _fold(buf, server, one, knobs)
    assert bool(commit)                          # deadline reached
    np.testing.assert_array_equal(np.asarray(srv["x"]), 1.0)
    assert float(buf.commits) == 1.0


def test_staleness_discount_downweights_without_bias():
    m = 2
    server = {"x": jnp.zeros(1)}
    knobs = knobs_of(Strategy("s", buffer_size=2, deadline_rounds=10,
                              staleness_discount=0.5))
    buf = init_buffer_state(server, m)
    first = jnp.asarray([True, False])
    second = jnp.asarray([False, True])
    x_old = {"x": jnp.full((m, 1), 4.0)}
    x_new = {"x": jnp.full((m, 1), 1.0)}
    buf, _, commit, _ = buffered_aggregate(
        buf, server, x_old, first, jnp.full((m,), 0.5), knobs, op=OP_MEAN,
        m_total=m, in_buffer_new=buf.in_buffer | first)
    assert not bool(commit)
    buf, srv, commit, _ = buffered_aggregate(
        buf, server, x_new, second, jnp.full((m,), 0.5), knobs, op=OP_MEAN,
        m_total=m, in_buffer_new=buf.in_buffer | second)
    assert bool(commit)
    # discounted mean: (0.5*4 + 1) / (0.5 + 1) = 2, between the stale (4)
    # and fresh (1) values but closer to fresh — down-weighted, not biased
    assert float(srv["x"][0]) == pytest.approx(2.0)
