"""Traced hyperparameter axes of the batched sweep core.

Two guarantees back the "traced-everything" design:

1. Substituting traced inputs for compile-time constants changes NOTHING
   numerically: every (hyperparameter point, seed) trajectory of
   ``make_batched_run_rounds`` — traced lr, traced gamma, traced Eq.-9
   ``p_base``, traced dataset arrays and partition — is bit-for-bit equal to
   a sequential ``make_run_rounds`` run with that point's knobs baked as
   constants (the pre-refactor execution model).
2. Because swept values are traced, a value-only ablation compiles ONCE per
   (algorithm, scheme): the runner's two jitted stages report a single cache
   entry across an alpha/sigma0/delta/lr/gamma sweep, and the executor's
   runner cache hands back the same object for specs differing only in
   swept values.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    init_fed_state,
    make_algorithm,
    make_link_process,
    make_run_rounds,
)
from repro.experiments import SweepSpec, make_classification_task, seed_keys
from repro.experiments.grid import (
    _RUNNER_CACHE,
    _runner_for,
    get_traced_task,
    make_cell_batch,
    point_base_probs,
    run_cell_batch,
)
from repro.optim import paper_decay, sgd

M, S_LOCAL, B = 8, 3, 4
SEEDS = (0, 1)
BASE = SweepSpec(seeds=SEEDS, num_clients=M, dim=16, hidden=16, classes=10,
                 n_per_class=60, n_train=480, per_client=24,
                 batch_size=B, local_steps=S_LOCAL, rounds=5, eval_every=2)
METRIC_KEYS = ("loss", "num_active")


def _constant_task(spec, alpha):
    """The constant-capturing task at one point's alpha (dataset + partition
    baked as jit constants, the pre-refactor data path)."""
    return make_classification_task(
        data_seed=spec.data_seed, num_clients=spec.num_clients, dim=spec.dim,
        classes=spec.classes, hidden=spec.hidden, n_per_class=spec.n_per_class,
        n_train=spec.n_train, alpha=alpha, per_client=spec.per_client,
        local_steps=spec.local_steps, batch_size=spec.batch_size)


def _sequential_point(spec, algo_name, scheme, point, seed, p_base_row,
                      chunks):
    """One trajectory on the sequential ``make_run_rounds`` path with the
    point's lr/gamma/alpha baked as constants; evals at chunk boundaries."""
    task = _constant_task(spec, point["alpha"])
    fed = dataclasses.replace(spec.cell_config(algo_name, scheme),
                              gamma=point["gamma"], alpha=point["alpha"],
                              sigma0=point["sigma0"], delta=point["delta"])
    algo = make_algorithm(fed)
    opt = sgd(paper_decay(point["lr"]))
    link = make_link_process(p_base_row, fed)
    run_rounds = make_run_rounds(task.loss_fn, opt, algo, link, fed,
                                 task.source, metric_keys=METRIC_KEYS,
                                 donate=False)
    ks = seed_keys(seed)
    st = init_fed_state(ks["state"], task.init_params(ks["params"]), fed,
                        algo, link, opt)
    ds = task.source.init(ks["ds"])
    collected, evals = [], []
    for c in chunks:
        st, ds, mets = run_rounds(st, ds, ks["data"], c)
        collected.append(mets)
        evals.append(task.eval_test(st.server))
    mets = jax.tree.map(lambda *xs: jnp.concatenate(xs), *collected)
    return st, mets, jnp.stack(evals)


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# Float tolerance where a batched trajectory meets its sequential twin on
# jax 0.9: the two are separately compiled programs, and XLA may fuse a
# reduction of the vmapped round in another order, one float32 ulp per
# affected round (1.9e-9 on 0.02-sized params, relative 1.0e-7, observed
# after 5 rounds). 2e-6 relative is ~16 ulps. Integer leaves (round
# counters, keys, active counts) stay exact: they carry no reduction.
TRAJ_RTOL, TRAJ_ATOL = 2e-6, 1e-8


def _assert_trees_close(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if np.issubdtype(x.dtype, np.floating):
            np.testing.assert_allclose(x, y, rtol=TRAJ_RTOL, atol=TRAJ_ATOL)
        else:
            np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("algo_name,scheme", [
    ("fedpbc", "bernoulli_tv"),
    ("fedavg", "markov_nonhom"),
])
def test_traced_points_match_static_sequential_bit_for_bit(algo_name, scheme):
    """lr x alpha axes (4 points x 2 seeds in ONE program) vs 8 independent
    constant-baked sequential runs: states, metrics, and in-scan evals must
    be bitwise identical per trajectory."""
    spec = dataclasses.replace(BASE, lrs=(0.05, 0.1), alphas=(0.1, 1.0))
    task = get_traced_task(spec)
    fed = spec.cell_config(algo_name, scheme)
    runner = _runner_for(spec, fed, task, METRIC_KEYS)
    batch = make_cell_batch(spec, fed, task)
    states, out = runner(batch)

    points = spec.hparam_points()
    S = len(SEEDS)
    assert out["evals"].shape == (len(points) * S, 3)  # rounds 2, 4, 5
    for pi, pt in enumerate(points):
        p_base = point_base_probs(spec, pt)
        for si, seed in enumerate(SEEDS):
            b = pi * S + si
            st_seq, mets_seq, evals_seq = _sequential_point(
                spec, algo_name, scheme, pt, seed, p_base[si],
                chunks=(2, 2, 1))
            _assert_trees_equal(jax.tree.map(lambda x: x[b], states), st_seq)
            for k in METRIC_KEYS:
                np.testing.assert_array_equal(
                    np.asarray(out["metrics"][k][b]), np.asarray(mets_seq[k]))
            np.testing.assert_array_equal(np.asarray(out["evals"][b]),
                                          np.asarray(evals_seq))


def test_traced_gamma_matches_static_sequential_bit_for_bit():
    """A gamma axis (Eq.-9 dynamics as traced scalars) must reproduce the
    gamma-baked link process, including the time-varying p_t the known-p
    algorithms consume: the same activations exactly, float state and
    losses within ``TRAJ_RTOL`` (the known-p weighting's reduction is one
    jax-0.9 ulp apart between the two programs)."""
    spec = dataclasses.replace(BASE, gammas=(0.1, 0.9), seeds=(0,))
    task = get_traced_task(spec)
    fed = spec.cell_config("fedavg_known_p", "bernoulli_tv")
    runner = _runner_for(spec, fed, task, METRIC_KEYS)
    batch = make_cell_batch(spec, fed, task)
    states, out = runner(batch)

    for pi, pt in enumerate(spec.hparam_points()):
        p_base = point_base_probs(spec, pt)
        st_seq, mets_seq, _ = _sequential_point(
            spec, "fedavg_known_p", "bernoulli_tv", pt, 0, p_base[0],
            chunks=(2, 2, 1))
        _assert_trees_close(jax.tree.map(lambda x: x[pi], states), st_seq)
        _assert_trees_close({k: out["metrics"][k][pi] for k in METRIC_KEYS},
                            {k: mets_seq[k] for k in METRIC_KEYS})


def test_value_ablation_reuses_one_compile():
    """An alpha/sigma0/delta/lr/gamma ablation is served by ONE compiled
    (init, scan) pair per (algorithm, scheme): re-running with entirely
    different swept values (same grid shape) adds zero compile-cache entries
    and zero executor runner-cache entries."""
    # distinct rounds/eval_every -> a runner of this test's own (a runner is
    # shared per structural key, so other tests' batch shapes would otherwise
    # legitimately add shape-keyed cache entries)
    spec = dataclasses.replace(BASE, rounds=4, eval_every=0,
                               lrs=(0.05, 0.1), alphas=(0.1, 1.0),
                               gammas=(0.1, 0.9), sigma0s=(1.0, 10.0),
                               deltas=(0.02, 0.1))
    run_cell_batch(spec, "fedpbc", "bernoulli_tv", metric_keys=METRIC_KEYS)
    fed = spec.cell_config("fedpbc", "bernoulli_tv")
    runner = _runner_for(spec, fed, get_traced_task(spec), METRIC_KEYS)
    if not hasattr(runner.scan_batch, "_cache_size"):
        pytest.skip("jax.jit cache introspection unavailable")
    assert runner.init_batch._cache_size() == 1
    assert runner.scan_batch._cache_size() == 1

    n_runners = len(_RUNNER_CACHE)
    spec2 = dataclasses.replace(spec, lrs=(0.2, 0.01), alphas=(0.5, 5.0),
                                gammas=(0.3, 0.7), sigma0s=(2.0, 5.0),
                                deltas=(0.001, 0.05))
    cells = run_cell_batch(spec2, "fedpbc", "bernoulli_tv",
                           metric_keys=METRIC_KEYS)
    assert len(cells) == 32 and len(_RUNNER_CACHE) == n_runners
    runner2 = _runner_for(spec2, spec2.cell_config("fedpbc", "bernoulli_tv"),
                          get_traced_task(spec2), METRIC_KEYS)
    assert runner2 is runner
    assert runner.init_batch._cache_size() == 1
    assert runner.scan_batch._cache_size() == 1


def test_period_override_shares_compile_and_changes_trajectory():
    """``fed_overrides=(("period", P),)`` is a traced hparam, not a compile
    knob: the runner cache zeroes ``period`` in its key, so two specs
    differing only in the override must hand back the SAME runner with no
    new jit entries — yet the traced ``hp["period"]`` input must actually be
    wired from the override, i.e. the trajectories must differ AND match a
    sequential run with that period baked into the link process.

    The algorithm is fedavg_known_p, which weights every arrival by its
    time-varying p_t: the period then moves the server update itself. (With
    8 clients over 5 rounds the Bernoulli activations alone can coincide
    across periods, as they do under jax 0.9's random bits.) Float state
    meets the sequential twin within ``TRAJ_RTOL``, activations exactly."""
    algo = "fedavg_known_p"
    spec20 = dataclasses.replace(BASE, rounds=5, eval_every=3, seeds=(0,),
                                 fed_overrides=(("period", 20),))
    spec40 = dataclasses.replace(spec20, fed_overrides=(("period", 40),))

    # the override reaches the traced input
    fed20 = spec20.cell_config(algo, "bernoulli_tv")
    batch20 = make_cell_batch(spec20, fed20, get_traced_task(spec20))
    np.testing.assert_array_equal(np.asarray(batch20.hparams["period"]),
                                  np.full((1,), 20.0, np.float32))

    cells20 = run_cell_batch(spec20, algo, "bernoulli_tv",
                             metric_keys=METRIC_KEYS, mesh=None)
    runner = _runner_for(spec20, fed20, get_traced_task(spec20), METRIC_KEYS)
    n_runners = len(_RUNNER_CACHE)
    has_introspection = hasattr(runner.scan_batch, "_cache_size")
    if has_introspection:
        n_entries = (runner.init_batch._cache_size()
                     + runner.scan_batch._cache_size())

    cells40 = run_cell_batch(spec40, algo, "bernoulli_tv",
                             metric_keys=METRIC_KEYS, mesh=None)
    # one compile serves both periods...
    assert len(_RUNNER_CACHE) == n_runners
    assert _runner_for(spec40, spec40.cell_config(algo, "bernoulli_tv"),
                       get_traced_task(spec40), METRIC_KEYS) is runner
    if has_introspection:
        assert (runner.init_batch._cache_size()
                + runner.scan_batch._cache_size()) == n_entries
    # ...but the trajectories differ: period shapes p_of_t, which the
    # known-p weighting divides every arrival by
    assert not np.array_equal(cells20[0].loss, cells40[0].loss)

    # and each matches the sequential path with its period BAKED into the
    # link process (cell_config carries the override into fed.period)
    for spec, cells in ((spec20, cells20), (spec40, cells40)):
        pt = spec.hparam_points()[0]
        p_base = point_base_probs(spec, pt)
        _, mets_seq, evals_seq = _sequential_point(
            spec, algo, "bernoulli_tv", pt, 0, p_base[0], chunks=(3, 2))
        np.testing.assert_array_equal(np.asarray(cells[0].num_active[0]),
                                      np.asarray(mets_seq["num_active"]))
        _assert_trees_close((cells[0].loss[0], cells[0].test_acc[0]),
                            (mets_seq["loss"], evals_seq))


def test_label_noise_shared_swap_reuses_compile_without_new_task():
    """The ROADMAP "traced dataset swaps" path: a same-shape label-noise
    variant of the dataset rides the traced ``shared`` input of an already
    compiled runner — no new task object, no new partition, zero new jit
    entries — and the swap is actually wired (trajectories change)."""
    import repro.experiments.grid as grid_mod
    from repro.experiments.tasks import with_label_noise

    spec = dataclasses.replace(BASE, rounds=4, eval_every=2)
    task = get_traced_task(spec)
    fed = spec.cell_config("fedpbc", "bernoulli_ti")
    runner = _runner_for(spec, fed, task, METRIC_KEYS)
    batch = make_cell_batch(spec, fed, task)
    states, out = runner(batch)
    has_introspection = hasattr(runner.scan_batch, "_cache_size")
    if has_introspection:
        n_entries = (runner.init_batch._cache_size()
                     + runner.scan_batch._cache_size())
    n_tasks = len(grid_mod._TRACED_TASK_CACHE)

    noisy = with_label_noise(task.shared, jax.random.PRNGKey(7), frac=0.5,
                             classes=spec.classes)
    # same shapes/dtypes, different labels, untouched features
    assert noisy["y"].shape == task.shared["y"].shape
    assert noisy["y"].dtype == task.shared["y"].dtype
    assert not np.array_equal(np.asarray(noisy["y"]),
                              np.asarray(task.shared["y"]))
    np.testing.assert_array_equal(np.asarray(noisy["x"]),
                                  np.asarray(task.shared["x"]))

    states2, out2 = runner(dataclasses.replace(batch, shared=noisy))
    if has_introspection:
        assert (runner.init_batch._cache_size()
                + runner.scan_batch._cache_size()) == n_entries
    assert len(grid_mod._TRACED_TASK_CACHE) == n_tasks
    # the variant reached the training loop and the in-scan eval
    assert not np.array_equal(np.asarray(out2["metrics"]["loss"]),
                              np.asarray(out["metrics"]["loss"]))
    assert not np.array_equal(np.asarray(out2["evals"]),
                              np.asarray(out["evals"]))


def test_hparam_points_flattening_and_result_coords():
    """Point-major flattening: every CellResult carries its coordinates, in
    ``itertools.product`` order over (lr, gamma, alpha, sigma0, delta)."""
    spec = dataclasses.replace(BASE, lrs=(0.05, 0.1), deltas=(0.02, 0.1))
    points = spec.hparam_points()
    assert [(p["lr"], p["delta"]) for p in points] == [
        (0.05, 0.02), (0.05, 0.1), (0.1, 0.02), (0.1, 0.1)]
    # run_cell is single-point only and must refuse BEFORE running anything
    from repro.experiments import run_cell
    with pytest.raises(ValueError, match="4 hyperparameter points"):
        run_cell(spec, "fedpbc", "bernoulli_ti")
    cells = run_cell_batch(spec, "fedpbc", "bernoulli_ti",
                           metric_keys=METRIC_KEYS)
    assert [c.hparams for c in cells] == points
    for c in cells:
        assert c.test_acc.shape == (len(SEEDS), 3)
        assert c.loss.shape == (len(SEEDS), spec.rounds)
        # un-swept knobs are recorded at their scalar defaults
        assert c.hparams["alpha"] == spec.alpha
        assert c.hparams["gamma"] == spec.gamma
