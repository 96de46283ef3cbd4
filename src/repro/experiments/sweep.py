"""Batched experiment runner (seed x hyperparameter axis) + the sweep CLI.

One `(algorithm, link-scheme)` grid cell of a paper table used to be S seeded
repetitions of one program; with the hyperparameter axis it is B = P x S
trajectories — P hyperparameter points (a flattened lr x gamma x alpha x
sigma0 x delta product) times S seeds. ``make_batched_run_rounds`` vmaps the
ENTIRE per-trajectory pipeline —

    init params -> init_fed_state -> K rounds (lax.scan) -> periodic eval

— over that one leading batch axis, so all B trajectories execute as ONE
compiled device program. *Everything that varies within a sweep enters as a
traced input*, carried by a ``CellBatch``:

- ``keys``     per-trajectory PRNG key bundles (leaves ``[B, 2]``);
- ``p_base``   per-trajectory Eq.-9 connection probabilities ``[B, m]``
  (alpha/sigma0/delta reach the program only through this input);
- ``hparams``  per-trajectory traced scalars (``lr``, ``gamma``, ``period``)
  the factories consume *inside* the trace — the optimizer's schedule and the
  link process are built from traced values, not baked closures;
- ``data``     per-trajectory ``ds_state`` (e.g. the Dirichlet(alpha)
  partition ``idx [B, m, per_client]``);
- ``shared``   the unbatched dataset arrays, traced but vmapped with
  ``in_axes=None`` so B trajectories share one device copy;
- ``algo_id``  per-trajectory algorithm index ``[B]`` into an
  ``AlgorithmSpec`` family table — the *algorithm axis*. When the runner is
  built from a spec (``repro.core.AlgorithmSpec``), client-start/aggregate
  lower to a branchless ``lax.switch``/select over the family's branch table,
  so every state-compatible algorithm (e.g. the whole
  fedavg/fedavg_all/fedavg_known_p/fedpbc family) shares ONE compiled program
  and the algorithm axis flattens into the batch dimension alongside points
  and seeds.

Only *structural* knobs still recompile: the (algorithm family, scheme) pair
(distinct ``algo_state``/``link_state`` pytree shapes and branch tables),
round counts, and array shapes (num_clients, per_client, model dims, batch
size).

``make_vmap_run_rounds`` — the PR-2 seed-axis API — is a thin wrapper that
runs a single-point batch with constant data/optimizer; migrated suites and
its bit-for-bit guarantees are unchanged.

CLI::

    PYTHONPATH=src python -m repro.experiments.sweep \
        --algos fedpbc,fedavg --schemes bernoulli_ti,markov_hom \
        --seeds 0,1,2 --lrs 0.05,0.1 --alphas 0.1,1.0 \
        --rounds 100 --clients 32 --out benchmarks/out/sweeps
"""
from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import FederationConfig
from repro.core.algorithms import Algorithm, AlgorithmSpec, as_algorithm
from repro.core.federated import (
    DEFAULT_METRIC_KEYS,
    init_fed_state,
    make_round_fn,
    make_round_step,
)
from repro.data.sources import DataSource
from repro.scale.buffer import STRATEGY_KNOB_FIELDS
from repro.sharding.specs import spec_for_shape

Pytree = Any


def seed_keys(seed: int):
    """The per-seed key bundle. Matches the historical layout of
    ``benchmarks/common.run_training`` (params=seed+1, state=seed+2,
    ds=seed+3, data=seed+4) so migrated suites keep their key protocol."""
    return {
        "params": jax.random.PRNGKey(seed + 1),
        "state": jax.random.PRNGKey(seed + 2),
        "ds": jax.random.PRNGKey(seed + 3),
        "data": jax.random.PRNGKey(seed + 4),
    }


def stack_seed_keys(seeds):
    """Stack per-seed key bundles into one [S]-batched pytree."""
    bundles = [seed_keys(s) for s in seeds]
    return jax.tree.map(lambda *ks: jnp.stack(ks), *bundles)


@dataclass
class CellBatch:
    """Everything one (algorithm-family, scheme) cell's compiled program
    consumes.

    All fields are pytrees; ``keys``/``p_base``/``hparams``/``data``/
    ``algo_id`` carry a leading ``[B]`` batch axis (B = algos x points x
    seeds), ``shared`` is unbatched (one device copy serves every
    trajectory). ``algo_id`` is the traced per-trajectory index into the
    runner's ``AlgorithmSpec`` table; the default ``()`` (no algorithm axis)
    keeps the historical single-algorithm program. Registered as a pytree so
    a batch can be sliced/saved/donated like any other JAX value.
    """

    keys: Pytree        # seed-key bundles, leaves [B, 2]
    p_base: Pytree      # [B, m] Eq.-9 connection probabilities
    hparams: Pytree     # dict of [B] traced scalars (lr, gamma, period, ...)
    data: Pytree        # per-trajectory ds_state (leaves [B, ...])
    shared: Pytree      # unbatched dataset arrays
    algo_id: Pytree = ()  # [B] int32 AlgorithmSpec indices, or () (no axis)

    @property
    def batch_size(self) -> int:
        return jax.tree.leaves(self.p_base)[0].shape[0]


jax.tree_util.register_dataclass(
    CellBatch,
    data_fields=["keys", "p_base", "hparams", "data", "shared", "algo_id"],
    meta_fields=[],
)


def make_batched_run_rounds(loss_fn: Callable, algorithm,
                            fed_cfg: FederationConfig, *,
                            optimizer_factory: Callable,
                            link_factory: Callable,
                            source_factory: Callable,
                            init_params: Callable,
                            num_rounds: int,
                            eval_every: int = 0,
                            eval_fn: Optional[Callable] = None,
                            metric_keys=DEFAULT_METRIC_KEYS,
                            use_kernel: bool = False,
                            cohort_size: Optional[int] = None,
                            buffered: bool = False,
                            shard_mesh=None,
                            carry_out: bool = False,
                            donate_carry: Optional[bool] = None):
    """Build the jitted B-trajectory runner for one grid cell.

    Args:
      algorithm: an ``Algorithm`` (single rule, static dispatch — the
        historical program), or an ``AlgorithmSpec`` family table. With a
        spec, the batch's traced per-trajectory ``algo_id`` selects each
        trajectory's rule through the family's branchless switch, so one
        compiled program serves every member; a batch without an algorithm
        axis (``algo_id=()``) binds the spec's first entry statically.
      optimizer_factory: ``hparams -> Optimizer`` (e.g.
        ``lambda hp: sgd(paper_decay(hp["lr"]))``); called on the traced
        per-trajectory hparam scalars inside the trace, so swept LRs share one
        compile.
      link_factory: ``(p_base [m], hparams) -> LinkProcess`` (e.g.
        ``lambda p, hp: make_link_process(p, fed_cfg, gamma=hp["gamma"])``).
      source_factory: ``shared -> DataSource`` whose ``init(key, data)``
        consumes the per-trajectory ``data`` pytree (see
        ``repro.data.sources.traced_classification_source``).
      init_params: ``key -> model params`` (per-trajectory model init).
      num_rounds: static total round count K.
      eval_every / eval_fn: when both set, ``eval_fn(server_params, shared)``
        runs every ``eval_every`` rounds *inside* the compiled program, under
        the contract "always at least one eval, the last at round K": a final
        eval fires at round K when K is not a multiple of ``eval_every`` —
        including K == 0, where the single eval measures the freshly
        initialized model (E is never 0). ``eval_every == K`` fires exactly
        one eval, at K. The result comes back as ``out["evals"] [B, E]`` with
        boundaries ``eval_rounds(...)``.
      use_kernel: route a fusable family's server aggregation through the
        backend-dispatched fused Pallas kernel (one pass per leaf, branch
        select inside the kernel body) instead of the XLA masked-mean
        switch; see ``repro.kernels.dispatch`` for backend resolution and
        the per-backend tolerance contract. The traced program shape is
        unchanged — one compiled (init, scan) pair still serves the whole
        family.
      cohort_size / buffered: the cross-device scale modes (``repro.scale``),
        requiring an ``AlgorithmSpec``. ``cohort_size=C`` subsamples C
        clients per round on device (stateless clients, O(C) round memory).
        ``buffered=True`` routes a fusable family's aggregation through the
        buffered semi-async engine, reading the per-trajectory strategy
        knobs (``repro.scale.STRATEGY_KNOB_FIELDS``) from ``hparams`` — the
        strategy axis is one more traced batched dimension, zero extra
        compiles.
      shard_mesh: a 2-D ``("batch", "model")`` mesh
        (``repro.launch.mesh.make_2d_mesh``) turning the runner into the
        sharded-LM execution path: the trajectory vmaps carry
        ``spmd_axis_name="batch"``, the round's client vmap carries
        ``spmd_axis_name="model"`` (local training parallel over clients,
        each client's model whole on its device), and the ``FedState`` is
        constrained so server parameters shard per-leaf over ``"model"``
        (``repro.sharding.spec_for_shape``) and client/optimizer stacks
        shard their leading client axis over ``"model"``. Before any
        cross-client reduction the local updates are gathered back to
        model-replicated (``gather_updates``), so the aggregation step is
        computed redundantly-but-identically on every device and
        introduces no divergence by construction. The remaining divergence
        source is XLA itself: per-client forward/backward compiles at
        per-device client shapes (m/model_axis rows instead of m), and on
        CPU the fusion chosen at a different shape can reassociate a
        reduction by ~1 ulp. Observed reach: the forward-only scalar loss
        telemetry in ``out["metrics"]`` (feeds neither gradients nor
        state), and in cohort mode occasionally the gradients themselves
        (~1e-8 in server params). The pinned shapes in
        ``tests/test_lm_sweep.py`` are bitwise across the board —
        state, evals and metrics — and deterministically so; at other
        shapes treat state/evals as allclose(1e-6) and metrics as
        allclose(1e-5). The final state is
        gathered to model-replicated so downstream host-side evals see
        plain batch-sharded arrays. Feed the result through
        ``repro.experiments.shard.run_sharded_2d``.
      carry_out: the resumable *scan-segment* mode (the adaptive-search
        driver's building block). The scan stage returns
        ``((states, ds_states), out)`` instead of ``(states, out)`` — the
        full [B]-batched ``(FedState, ds_state)`` carry comes back as device
        arrays, so a caller can run ``num_rounds``-sized segments back to
        back: ``carry = run.init(batch)``, then repeatedly
        ``carry, out = run.step(carry, batch)``. Because the round step's
        data key is a pure function of the carried round counter
        (``make_round_step`` folds ``state.round`` into ``data_key``) and
        the link/optimizer state ride the carry, k chained segments are
        bit-for-bit equal to one uninterrupted ``k * num_rounds`` program
        with the same eval cadence (``tests/test_search.py``).
      donate_carry: donate the incoming carry to the scan stage so it
        updates in place instead of doubling the [B]-state footprint: the
        ``(st, ds)`` carry in ``carry_out`` mode, the FedState alone in the
        one-shot mode (its ``ds`` may be the batch's own partition table,
        which later calls reuse). Defaults to backend != "cpu" — the same
        gate as ``make_run_rounds`` (CPU ignores donation noisily). After
        ``run.step(carry, ...)`` the passed carry is dead on donating
        backends; rebind, never reuse.

    Returns ``run(batch: CellBatch) -> (states, out)`` where ``states`` is a
    [B]-batched ``FedState`` and ``out["metrics"]`` maps each metric key to a
    ``[B, K, ...]`` array. Each trajectory is bit-for-bit equal to an
    independent sequential ``make_run_rounds`` run with the same key bundle
    and that point's knobs baked as constants — ``tests/test_sweep.py`` and
    ``tests/test_traced_axes.py`` enforce this.

    The runner is two compiled programs, not one: a (cheap) batched init and
    the batched round scan, with the [B]-batched state passed BETWEEN them as
    a device array. Fusing init into the same program as the scan lets XLA
    compile the scan body in a different fusion context, which on CPU can
    perturb float reductions by 1 ulp — the split keeps the scan stage's
    abstract signature identical in structure to ``make_run_rounds`` and is
    what makes per-trajectory bitwise equality hold. The two jitted stages
    are exposed as ``run.init_batch`` / ``run.scan_batch`` so callers (the
    compile-counter test, benchmarks) can read their compile-cache sizes.
    """
    do_eval = eval_fn is not None and eval_every > 0
    n_chunks, rem = divmod(num_rounds, eval_every) if do_eval else (0, num_rounds)
    scale_mode = buffered or cohort_size is not None
    if scale_mode and not isinstance(algorithm, AlgorithmSpec):
        raise ValueError(
            "cohort_size/buffered need an AlgorithmSpec runner (got "
            f"{type(algorithm).__name__})")
    # stateful rules take the sparse cohort path; only fusable families
    # thread a BufferState
    has_buffer = scale_mode and isinstance(algorithm, AlgorithmSpec) \
        and algorithm.fusable
    if shard_mesh is not None and not (
            {"batch", "model"} <= set(shard_mesh.axis_names)):
        raise ValueError(
            f'shard_mesh needs ("batch", "model") axes, got '
            f"{shard_mesh.axis_names}")
    spmd_model = "model" if shard_mesh is not None else None

    def _wsc(x, spec):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(shard_mesh, spec))

    def _replicate(tree):
        """Gather every leaf to model-replicated (specs written here are the
        per-trajectory view — the trajectory vmap's spmd_axis_name prepends
        "batch" on the mapped dim)."""
        if shard_mesh is None:
            return tree
        return jax.tree.map(lambda x: _wsc(x, P()), tree)

    gather = _replicate if shard_mesh is not None else None
    if eval_fn is not None and shard_mesh is not None:
        _base_eval = eval_fn
        # in-program evals reduce over the dataset: gather the (possibly
        # model-sharded) server params first so the reduction is computed
        # identically on every device
        eval_fn = lambda params, shared: _base_eval(_replicate(params), shared)  # noqa: E731

    def _constrain_state(st):
        """Pin the carried FedState's placement: server per-leaf over
        "model" (tensor sharding), client/optimizer stacks over their
        leading client axis. Constraining the scan carry keeps the layout
        stable across rounds instead of letting GSPMD re-derive it."""
        if shard_mesh is None:
            return st

        def client_leaf(x):
            return _wsc(x, P("model")) if x.ndim >= 1 else x

        return dataclasses.replace(
            st,
            server=jax.tree.map(
                lambda x: _wsc(x, spec_for_shape(x.shape, shard_mesh)),
                st.server),
            clients=jax.tree.map(client_leaf, st.clients),
            opt_state=jax.tree.map(client_leaf, st.opt_state))

    def _bound(algo_id):
        """Resolve the per-trajectory dispatch: a traced ``algo_id`` scalar
        selects through the spec's switch; an absent axis (the empty-pytree
        default) is the historical static program."""
        if isinstance(algo_id, tuple) and algo_id == ():
            algo_id = 0
        return as_algorithm(algorithm, algo_id, use_kernel=use_kernel)

    def init_point(keys, p_base, hparams, data, shared, algo_id):
        algo = _bound(algo_id)
        optimizer = optimizer_factory(hparams)
        link = link_factory(p_base, hparams)
        source = source_factory(shared)
        params = init_params(keys["params"])
        st = init_fed_state(keys["state"], params, fed_cfg, algo, link,
                            optimizer,
                            stateless_clients=cohort_size is not None,
                            buffered=has_buffer)
        return _constrain_state(st), source.init(keys["ds"], data)

    def scan_point(st, ds, data_key, p_base, hparams, shared, algo_id):
        optimizer = optimizer_factory(hparams)
        link = link_factory(p_base, hparams)
        source = source_factory(shared)
        if scale_mode:
            # the scale engines dispatch the spec themselves (they need the
            # family table, not a bound Algorithm)
            aid = 0 if (isinstance(algo_id, tuple) and algo_id == ()) \
                else algo_id
            strat = ({k: hparams[k] for k in STRATEGY_KNOB_FIELDS}
                     if buffered else None)
            round_fn = make_round_fn(loss_fn, optimizer, algorithm, link,
                                     fed_cfg, spmd_axis_name=spmd_model,
                                     algo_id=aid, strategy=strat,
                                     cohort_size=cohort_size,
                                     gather_updates=gather)
        else:
            round_fn = make_round_fn(loss_fn, optimizer, _bound(algo_id),
                                     link, fed_cfg,
                                     spmd_axis_name=spmd_model,
                                     gather_updates=gather)
        step = make_round_step(round_fn, source)

        def body(carry, _):
            st, ds = carry
            st, ds, mets = step(st, ds, data_key)
            return (_constrain_state(st), ds), {k: mets[k] for k in metric_keys}

        def run_span(carry, length):
            return jax.lax.scan(body, carry, None, length=length)

        if not do_eval:
            (st, ds), mets = run_span((st, ds), num_rounds)
            if carry_out:
                return (st, ds), {"metrics": mets}
            # final all-gather: downstream consumers (host-side evals,
            # rows()) see model-replicated, batch-sharded state
            return _replicate(st), {"metrics": mets}

        def chunk(carry, _):
            carry, mets = run_span(carry, eval_every)
            with jax.named_scope("fed.eval"):
                evals = eval_fn(carry[0].server, shared)
            return carry, (mets, evals)

        carry, (mets, evals) = jax.lax.scan(chunk, (st, ds), None,
                                            length=n_chunks)
        # [E, eval_every, ...] -> [E * eval_every, ...]
        mets = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), mets)
        if rem or n_chunks == 0:
            # the remainder tail, plus the >= 1 eval guarantee: at K == 0
            # (rem == n_chunks == 0) this runs a zero-length span and evals
            # the freshly initialized model once
            carry, tail = run_span(carry, rem)
            mets = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], 0), mets, tail)
            with jax.named_scope("fed.eval"):
                last = eval_fn(carry[0].server, shared)
            evals = jnp.concatenate([evals, last[None]])
        st, ds = carry
        if carry_out:
            return (st, ds), {"metrics": mets, "evals": evals}
        return _replicate(st), {"metrics": mets, "evals": evals}

    # the trajectory axis is "batch" on every mesh; without one the name
    # binds nothing
    init_batch = jax.jit(jax.vmap(init_point, in_axes=(0, 0, 0, 0, None, 0),
                                  spmd_axis_name="batch"))
    # carry_out segments update the [B]-state in place (donated (st, ds))
    # so chaining rungs never doubles the state footprint; the one-shot
    # mode donates the FedState its own init stage just made, so a call
    # holds one copy of the client stacks, not an input and an output
    # (a full-width LM's [B, m, P] float32 clients fill a chip otherwise)
    if donate_carry is None:
        donate_carry = jax.default_backend() != "cpu"  # CPU ignores donation
    donate = ((0, 1) if carry_out else (0,)) if donate_carry else ()
    scan_batch = jax.jit(jax.vmap(scan_point,
                                  in_axes=(0, 0, 0, 0, 0, None, 0),
                                  spmd_axis_name="batch"),
                         donate_argnums=donate)

    def placed(batch: CellBatch):
        """Trace and run under the mesh the batch is laid out on, when it
        spans several devices (``jax.set_mesh``): that context is how the
        kernel dispatch layer knows to wrap its Mosaic kernels, which XLA
        cannot partition, in a ``shard_map`` (``repro.kernels.dispatch``)."""
        mesh = shard_mesh
        if mesh is None:
            sh = getattr(batch.p_base, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
                mesh = sh.mesh
        return contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh)

    def init(batch: CellBatch):
        """The batched init stage alone: the [B] (FedState, ds_state) carry."""
        with placed(batch):
            return init_batch(batch.keys, batch.p_base, batch.hparams,
                              batch.data, batch.shared, batch.algo_id)

    def step(carry, batch: CellBatch):
        """One scan dispatch from an existing carry. In ``carry_out`` mode
        this is the resumable segment: returns ``(next_carry, out)`` and (on
        donating backends) consumes the passed carry's buffers."""
        st, ds = carry
        with placed(batch):
            return scan_batch(st, ds, batch.keys["data"], batch.p_base,
                              batch.hparams, batch.shared, batch.algo_id)

    def run(batch: CellBatch):
        return step(init(batch), batch)

    def lower(batch: CellBatch):
        """The init and scan stages lowered for ``batch`` as ``run``
        dispatches them (``jax.stages.Lowered``; the scan's carry is the
        shape the init stage returns)."""
        args = (batch.keys, batch.p_base, batch.hparams, batch.data,
                batch.shared, batch.algo_id)
        with placed(batch):
            st, ds = jax.eval_shape(init_batch, *args)
            return (init_batch.lower(*args),
                    scan_batch.lower(st, ds, batch.keys["data"],
                                     batch.p_base, batch.hparams,
                                     batch.shared, batch.algo_id))

    run.init = init
    run.step = step
    run.lower = lower
    run.init_batch = init_batch
    run.scan_batch = scan_batch
    run.shard_mesh = shard_mesh
    run.carry_out = carry_out
    return run


def make_vmap_run_rounds(loss_fn: Callable, optimizer, algorithm: Algorithm,
                         fed_cfg: FederationConfig, source, *,
                         link_factory: Callable,
                         init_params: Callable,
                         num_rounds: int,
                         eval_every: int = 0,
                         eval_fn: Optional[Callable] = None,
                         metric_keys=DEFAULT_METRIC_KEYS):
    """The PR-2 seed-axis runner: S seeds of one cell as one program, with the
    optimizer and the dataset (a regular constant-capturing ``DataSource``)
    baked at build time.

    Now a thin wrapper over ``make_batched_run_rounds`` running a single
    hyperparameter point: hparams/data/shared are empty pytrees, so the traced
    program is the historical one and per-seed trajectories remain bit-for-bit
    equal to the sequential path (``tests/test_sweep.py``).

    Returns ``run(keys, p_base) -> (states, out)`` where ``keys`` is a
    ``stack_seed_keys`` bundle and ``p_base`` is ``[S, m]``.
    """
    core = make_batched_run_rounds(
        loss_fn, algorithm, fed_cfg,
        optimizer_factory=lambda hp: optimizer,
        link_factory=lambda p, hp: link_factory(p),
        source_factory=lambda shared: DataSource(
            lambda key, data: source.init(key), source.sample, source.name),
        init_params=init_params,
        num_rounds=num_rounds,
        eval_every=eval_every,
        eval_fn=(lambda params, shared: eval_fn(params))
                if eval_fn is not None else None,
        metric_keys=metric_keys)

    def run(keys, p_base):
        return core(CellBatch(keys=keys, p_base=p_base, hparams={}, data=(),
                              shared=()))

    run.init_batch = core.init_batch
    run.scan_batch = core.scan_batch
    return run


def eval_rounds(num_rounds: int, eval_every: int):
    """Round indices (1-based) at which the runner's evals fire.

    Contract (mirrored by ``make_batched_run_rounds``): at least one eval,
    the last at ``num_rounds`` — so ``eval_every == num_rounds`` fires exactly
    one final eval, and ``num_rounds == 0`` evals the initial model once (at
    "round 0"). ``eval_every <= 0`` means a single eval at the final round.
    """
    if eval_every <= 0:
        return [num_rounds]
    n_chunks, rem = divmod(num_rounds, eval_every)
    out = [eval_every * (i + 1) for i in range(n_chunks)]
    if rem or not out:
        out.append(num_rounds)
    return out


def _float_list(text: str):
    return tuple(float(v) for v in text.split(",")) if text else ()


def main(argv=None) -> None:
    import argparse

    # lazy: grid imports this module
    from repro.experiments.grid import ALGOS, SCHEMES, SweepSpec, run_sweep
    from repro.experiments.results import ResultsStore

    ap = argparse.ArgumentParser(
        description="Run a (algorithm x scheme x hyperparameter x seed) sweep "
                    "on the batched engine and append results to a JSONL/npz "
                    "store. Each --lrs/--gammas/--alphas/--sigma0s/--deltas "
                    "axis — and every state-compatible group of --algos "
                    "(e.g. fedpbc,fedavg,fedavg_all,fedavg_known_p) — is "
                    "swept inside ONE compiled program per "
                    "(algorithm family, scheme).")
    ap.add_argument("--algos", default="fedpbc,fedavg",
                    help=f"comma list from {','.join(ALGOS)}")
    ap.add_argument("--schemes", default="bernoulli_ti",
                    help=f"comma list from {','.join(SCHEMES)}")
    ap.add_argument("--seeds", default="0,1,2", help="comma list of ints")
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--gamma", type=float, default=0.5)
    ap.add_argument("--delta", type=float, default=0.02)
    ap.add_argument("--sigma0", type=float, default=10.0)
    ap.add_argument("--lrs", default="", help="comma list; hyperparameter "
                    "axis overriding --lr (traced, no recompile)")
    ap.add_argument("--gammas", default="", help="axis overriding --gamma")
    ap.add_argument("--alphas", default="", help="axis overriding --alpha")
    ap.add_argument("--sigma0s", default="", help="axis overriding --sigma0")
    ap.add_argument("--deltas", default="", help="axis overriding --delta")
    ap.add_argument("--task", default="classification",
                    choices=("classification", "lm"),
                    help="client workload: the paper's classification task "
                    "or the smollm-class reduced LM (next-token loss over "
                    "the styled byte-level corpus)")
    ap.add_argument("--lm-d-model", type=int, default=64,
                    help="LM task: reduced model width")
    ap.add_argument("--lm-layers", type=int, default=2,
                    help="LM task: reduced layer count")
    ap.add_argument("--lm-seq", type=int, default=32,
                    help="LM task: training sequence length")
    ap.add_argument("--cohort", type=int, default=None,
                    help="per-round cohort size C (cross-device scale mode: "
                    "stateless clients, O(C) round memory)")
    ap.add_argument("--buffer-size", type=int, default=0,
                    help="add a buffered semi-async strategy arm committing "
                    "when this many updates have arrived (0: sync only)")
    ap.add_argument("--deadline-rounds", type=int, default=4,
                    help="buffered arm: commit after this many rounds even "
                    "if the buffer has not filled")
    ap.add_argument("--staleness-discount", type=float, default=0.0,
                    help="buffered arm: per-round decay of the standing "
                    "buffer, in [0, 1)")
    ap.add_argument("--wait-for-full", action="store_true",
                    help="buffered arm: commit ONLY when the buffer fills "
                    "(ignore the deadline)")
    ap.add_argument("--buffered-only", action="store_true",
                    help="drop the sync arm when --buffer-size is set")
    ap.add_argument("--out", default="benchmarks/out/sweeps",
                    help="results-store directory (JSONL + npz)")
    ap.add_argument("--suite", default="cli", help="suite tag on the records")
    args = ap.parse_args(argv)

    from repro.scale import SYNC, Strategy

    strategies = (SYNC,)
    if args.buffer_size:
        arm = Strategy("buffered", wait_for_full=args.wait_for_full,
                       buffer_size=args.buffer_size,
                       deadline_rounds=args.deadline_rounds,
                       staleness_discount=args.staleness_discount)
        strategies = (arm,) if args.buffered_only else (SYNC, arm)
    spec = SweepSpec(
        algorithms=tuple(args.algos.split(",")),
        schemes=tuple(args.schemes.split(",")),
        seeds=tuple(int(s) for s in args.seeds.split(",")),
        rounds=args.rounds, eval_every=args.eval_every,
        num_clients=args.clients, local_steps=args.local_steps,
        lr=args.lr, alpha=args.alpha, gamma=args.gamma, delta=args.delta,
        sigma0=args.sigma0,
        lrs=_float_list(args.lrs), gammas=_float_list(args.gammas),
        alphas=_float_list(args.alphas), sigma0s=_float_list(args.sigma0s),
        deltas=_float_list(args.deltas),
        strategies=strategies, cohort_size=args.cohort,
        task=args.task, lm_d_model=args.lm_d_model,
        lm_layers=args.lm_layers, lm_seq=args.lm_seq)
    store = ResultsStore(args.out)
    print("sweep,scheme,algo,strategy,hparams,seeds,test_acc_mean,"
          "test_acc_ci95,train_acc_mean", flush=True)
    for cell in run_sweep(spec, store=store, suite=args.suite):
        s = cell.summary()
        hp = ";".join(f"{k}={v:g}" for k, v in sorted(cell.hparams.items()))
        print(f"sweep,{cell.scheme},{cell.algo},{cell.strategy},{hp},"
              f"{len(cell.seeds)},"
              f"{s['test_acc']['mean']:.4f},{s['test_acc']['ci95']:.4f},"
              f"{s['train_acc']['mean']:.4f}", flush=True)
    print(f"# results appended to {store.path}", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
