"""Data sampling: each draw resolved inside the client's index row, features
and label fetched by one row gather of a packed table.

The reference throughout is the element-gather formula the sources used
before: ``sel = client_idx[clients[:, None, None], pick]``, then ``x[sel]``
and ``y[sel]`` (or ``toks[sel]``). The batches must equal it bit for bit,
at narrow and wide index rows, and so must a whole ``run_sweep`` call.
"""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.sources import (
    DataSource,
    classification_source,
    traced_classification_source,
    traced_lm_source,
)
from repro.experiments import SweepSpec, grid, run_sweep
from repro.experiments import tasks as tasks_mod
from repro.experiments.tasks import make_traced_classification_task

S, B = 3, 4
CLASSES = 10


def old_sel(key, client_idx, clients, local_steps=S, batch_size=B):
    """The element-gather formula, kept here as the reference."""
    m, per_client = client_idx.shape
    rows = jnp.arange(m) if clients is None else clients
    pick = jax.random.randint(
        key, (rows.shape[0], local_steps, batch_size), 0, per_client)
    return client_idx[rows[:, None, None], pick]


def old_traced_classification_source(shared, *, local_steps, batch_size):
    def sample(ds_state, t, key):
        sel = old_sel(key, ds_state["idx"], None, local_steps, batch_size)
        return {"x": shared["x"][sel], "y": shared["y"][sel]}, ds_state

    return DataSource(lambda key, data: data, sample, "old")


def _case(kind, per_client, seed):
    """(source, ds_state, index table, reference batch builder) for one
    source kind."""
    rng = np.random.default_rng(seed)
    n, m = 3 * per_client, 6
    idx = jnp.asarray(rng.integers(0, n, size=(m, per_client)), jnp.int32)
    if kind == "traced_lm":
        toks = jnp.asarray(rng.integers(0, 50_000, size=(n, 9)), jnp.int32)
        src = traced_lm_source({"toks": toks}, local_steps=S, batch_size=B)

        def ref(sel):
            seqs = toks[sel]
            return {"tokens": seqs[..., :-1], "labels": seqs[..., 1:]}

        return src, {"idx": idx}, idx, ref
    # the labels cover 0 and the top class; the features hold a float32
    # denormal, a negative zero and a NaN, which must all come back as bits
    y = jnp.asarray(np.arange(n) % CLASSES, jnp.int32)
    shape = (n, 3, 4) if kind == "classification" else (n, 32)
    xs = rng.normal(size=shape).astype(np.float32)
    xs.reshape(n, -1)[:3, 0] = [1e-40, -0.0, np.nan]
    x = jnp.asarray(xs)

    def ref(sel):
        return {"x": x[sel], "y": y[sel]}

    if kind == "classification":
        src = classification_source(x, y, idx, local_steps=S, batch_size=B)
        return src, src.init(jax.random.PRNGKey(0)), idx, ref
    src = traced_classification_source({"x": x, "y": y}, local_steps=S,
                                       batch_size=B)
    return src, {"idx": idx}, idx, ref


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("per_client", [64, 1000])
@pytest.mark.parametrize("kind", ["classification", "traced_classification",
                                  "traced_lm"])
def test_batches_equal_element_gather_formula(kind, per_client, seed):
    src, ds, idx, ref = _case(kind, per_client, seed)
    key = jax.random.PRNGKey(1000 + seed)
    cohort = jnp.asarray([4, 0, 4, 2], jnp.int32)
    got = {"sample": jax.jit(src.sample)(ds, 0, key)[0],
           "sample_cohort": jax.jit(src.sample_cohort)(ds, 0, key, cohort)[0]}
    want = {"sample": ref(old_sel(key, idx, None)),
            "sample_cohort": ref(old_sel(key, idx, cohort))}
    for mode in got:
        assert got[mode].keys() == want[mode].keys()
        for name, w in want[mode].items():
            g = got[mode][name]
            assert g.dtype == w.dtype and g.shape == w.shape, (mode, name)
            np.testing.assert_array_equal(_bits(g), _bits(w),
                                          err_msg=f"{mode} {name}")
    if kind != "traced_lm":
        labels = np.asarray(got["sample"]["y"])
        assert {0, CLASSES - 1} <= set(labels.ravel().tolist())


def _primitives(jaxpr, out):
    """Every equation of ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


@pytest.mark.parametrize("mode", ["sample", "sample_cohort"])
def test_sample_gathers_rows_of_packed_table_only(mode):
    """At mlp-family's shapes (m=100, per_client 64, 5 steps of 32, 5,000
    examples of 32 features) the round's sampling gathers whole rows only:
    exactly one gather of the packed ``[n, d+1]`` table, and none of single
    elements of the ``[m, per_client]`` index table; a cohort round adds
    one gather of its clients' whole index rows."""
    m, per_client, s, b = 100, 64, 5, 32
    task = make_traced_classification_task(num_clients=m,
                                           per_client=per_client,
                                           local_steps=s, batch_size=b)
    shared = {k: task.shared[k] for k in ("x", "y")}
    n, d = shared["x"].shape
    idx = jax.ShapeDtypeStruct((m, per_client), jnp.int32)
    cohort = jnp.arange(0, m, 4, dtype=jnp.int32)

    def sample(sh, ix, key):
        src = traced_classification_source(sh, local_steps=s, batch_size=b)
        if mode == "sample":
            return src.sample({"idx": ix}, 0, key)[0]
        return src.sample_cohort({"idx": ix}, 0, key, cohort)[0]

    jaxpr = jax.make_jaxpr(sample)(shared, idx, jax.random.PRNGKey(0))
    gathers = [e for e in _primitives(jaxpr.jaxpr, [])
               if e.primitive.name == "gather"]
    operands = sorted(tuple(e.invars[0].aval.shape) for e in gathers)
    want = [(n, d + 1)] if mode == "sample" else [(m, per_client), (n, d + 1)]
    assert operands == sorted(want)
    for e in gathers:
        row = e.invars[0].aval.shape[-1]
        assert e.params["slice_sizes"][-1] == row, e


class _Capture:
    """Keeps what a batched runner's latest call returned."""

    def __init__(self, inner, kept):
        self.inner, self.kept = inner, kept

    def __call__(self, *args, **kwargs):
        out = self.inner(*args, **kwargs)
        self.kept.append(out)
        return out

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _sweep(spec):
    """``run_sweep(spec)`` on freshly built runners: its rows and the server
    parameters of each runner call."""
    kept = []
    build = grid.make_batched_run_rounds
    with mock.patch.dict(grid._RUNNER_CACHE, clear=True), \
            mock.patch.object(grid, "make_batched_run_rounds",
                              lambda *a, **kw: _Capture(build(*a, **kw),
                                                        kept)):
        cells = run_sweep(spec)
    return cells, [jax.device_get(states.server) for states, _ in kept]


def test_run_sweep_equals_element_gather_formula():
    """A short run_sweep of the mlp-family grid (4 algorithms x 2 lrs x 2
    seeds, m=100, 5 local steps of 32) ends with the same server parameters
    and rows, bit for bit, as the same call sampling by the old formula."""
    spec = SweepSpec(
        algorithms=("fedpbc", "fedavg", "fedavg_all", "fedavg_known_p"),
        schemes=("bernoulli_tv",), seeds=(11, 12), lrs=(0.05, 0.1),
        rounds=3, eval_every=3, num_clients=100, local_steps=5,
        batch_size=32, data_seed=11, per_client=64, alpha=0.1, sigma0=10.0,
        delta=0.02, gamma=0.5)
    new_cells, new_servers = _sweep(spec)
    with mock.patch.object(tasks_mod, "traced_classification_source",
                           old_traced_classification_source):
        old_cells, old_servers = _sweep(spec)
    assert len(new_servers) == len(old_servers) == 1
    for a, b in zip(jax.tree.leaves(new_servers), jax.tree.leaves(old_servers)):
        np.testing.assert_array_equal(a, b)
    assert len(new_cells) == len(old_cells) == 8
    for c_new, c_old in zip(new_cells, old_cells):
        for field in ("loss", "num_active", "test_acc", "train_acc"):
            np.testing.assert_array_equal(getattr(c_new, field),
                                          getattr(c_old, field))

