"""Federated round engine.

A round (Alg. 1 of the paper) is one pure, jit-able function:

    1. sample the link process -> active mask A^t;
    2. every client runs ``s`` local optimizer steps from its start params
       (vmap over the client axis — or sharded over the "pod" axis in the
       ``pod_silo`` placement);
    3. the aggregation rule updates server + client params (postponed
       broadcast for FedPBC, instant for FedAvg-style baselines).

The engine is model-agnostic: the caller provides ``loss_fn(params, batch)``
and a per-client batch pytree with a leading ``[m, ...]`` axis.

Two execution modes share the same single-round primitive:

- ``round_fn(state, batches)`` — one round per dispatch, the composable
  building block (callers feed host- or device-generated batches);
- ``run_rounds(state, ds_state, data_key, num_rounds)`` — K rounds inside ONE
  ``jax.lax.scan`` over a device-resident ``DataSource``
  (``repro.data.sources``), with donated state buffers and stacked per-round
  metrics. This removes the per-round dispatch + H2D cost that dominates
  long-horizon simulations (thousands of rounds x many link schemes).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FederationConfig
from repro.core.algorithms import (
    Algorithm,
    AlgorithmSpec,
    _tile,
    as_algorithm,
    bcast_where,
    make_algorithm,
)
from repro.core.connectivity import LinkProcess
from repro.models.flags import scan_unroll

Pytree = Any


@dataclass
class FedState:
    server: Pytree
    clients: Pytree          # leading [m, ...] axis
    opt_state: Pytree        # per-client optimizer state, [m, ...]
    algo_state: Pytree
    link_state: Pytree
    round: jnp.ndarray       # scalar int32
    key: jnp.ndarray
    # staleness bookkeeping (Prop. 2): last round each uplink was active
    last_active: jnp.ndarray  # [m] int32
    # buffered semi-async aggregation (repro.scale.buffer): a BufferState
    # in buffered modes, () for the synchronous engine
    buffer: Pytree = ()


def init_fed_state(key, server_params, fed_cfg: FederationConfig,
                   algorithm, link: LinkProcess, optimizer, *,
                   stateless_clients: bool = False,
                   buffered: bool = False) -> FedState:
    """``algorithm`` may be an ``Algorithm`` or an ``AlgorithmSpec`` (whose
    unified ``init`` is dispatch-independent: every family member shares one
    state container).

    ``stateless_clients``: cohort (cross-device) mode — no ``[m, ...]``
    client params / optimizer state is materialized; every sampled client
    trains from the server model with a fresh optimizer, so per-round
    client memory is O(C). ``buffered``: thread a ``BufferState``
    (``repro.scale.buffer``) for the semi-async engine.
    """
    algorithm = as_algorithm(algorithm)
    m = fed_cfg.num_clients
    k_link, k_state = jax.random.split(key)
    if stateless_clients:
        clients, opt_state = (), ()
    else:
        clients = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (m,) + x.shape).copy(),
            server_params)
        opt_state = jax.vmap(optimizer.init)(clients)
    buffer = ()
    if buffered:
        from repro.scale.buffer import init_buffer_state
        buffer = init_buffer_state(server_params, m)
    return FedState(
        server=server_params,
        clients=clients,
        opt_state=opt_state,
        algo_state=algorithm.init(server_params, m),
        link_state=link.init(k_link),
        round=jnp.int32(0),
        key=k_state,
        last_active=jnp.full((m,), -1, jnp.int32),
        buffer=buffer,
    )


def local_steps(loss_fn, optimizer, params, opt_state, batches, s: int):
    """Run ``s`` local optimizer steps; ``batches`` has a leading [s, ...] axis
    (one mini-batch per local step). Returns (params', opt_state', mean_loss).

    Local training is deterministic given the batches: all randomness lives in
    the link process and the ``DataSource`` (stochastic local algorithms would
    take their keys via ``batches`` leaves so the scan stays key-free here).
    """

    def step(carry, batch):
        p, o = carry
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        p, o = optimizer.update(p, o, g)
        return (p, o), loss

    (params, opt_state), losses = jax.lax.scan(step, (params, opt_state), batches,
                                               unroll=scan_unroll())
    return params, opt_state, losses.mean()


def make_round_fn(loss_fn: Callable, optimizer, algorithm,
                  link: LinkProcess, fed_cfg: FederationConfig,
                  spmd_axis_name: Optional[str] = None,
                  algo_id=0, use_kernel: bool = False,
                  strategy=None, cohort_size: Optional[int] = None,
                  gather_updates: Optional[Callable] = None):
    """Build the jit-able round function.

    ``algorithm``: an ``Algorithm``, or an ``AlgorithmSpec`` table bound at
    ``algo_id`` — which may be a *traced* scalar, in which case the round's
    client-start/aggregate lower to the family's branchless switch and one
    round function serves every member.

    ``spmd_axis_name``: mesh axis the client dimension is sharded over in the
    ``pod_silo`` placement (vmap's spmd_axis_name); None for simulated /
    stacked_data placements.

    ``use_kernel``: route a fusable family's server aggregation through the
    backend-dispatched fused Pallas kernel (``repro.kernels.dispatch``)
    instead of the XLA masked-mean switch. Ignored for an already-bound
    ``Algorithm`` (its aggregation path is baked).

    ``strategy`` / ``cohort_size``: the cross-device scale modes
    (``repro.scale``). A non-None ``strategy`` (a ``Strategy`` or a traced
    knob mapping) routes a fusable family's aggregation through the
    buffered semi-async engine; a non-None ``cohort_size`` makes the round
    subsample C clients on device (stateless clients, O(C) round memory)
    and requires a source-aware step (the returned round function carries
    ``needs_source`` and the signature
    ``round_fn(state, ds_state, k_data, source)``). Both require an
    ``AlgorithmSpec`` (the engine needs the family table, not a bound
    ``Algorithm``). None/None is the historical synchronous trace,
    untouched.

    ``gather_updates``: optional hook applied to ``(x_star, losses)`` right
    after the client vmap, before any cross-client reduction. The 2-D sweep
    path uses it to gather model-axis-sharded local updates back to
    replicated (``repro.experiments.sweep``), so every device performs the
    aggregation redundantly but identically — bit-for-bit with the
    unsharded trace. None is the identity.
    """
    if strategy is not None or cohort_size is not None:
        return _make_scale_round_fn(loss_fn, optimizer, algorithm, link,
                                    fed_cfg, spmd_axis_name, algo_id,
                                    strategy, cohort_size, gather_updates)
    algorithm = as_algorithm(algorithm, algo_id, use_kernel=use_kernel)
    s = fed_cfg.local_steps

    def round_fn(state: FedState, batches) -> tuple:
        """batches: pytree with leading [m, s, ...] (per client, per step)."""
        key, k_link = jax.random.split(state.key)
        with jax.named_scope("fed.link"):
            active, p_t, link_state = link.sample(
                state.link_state, state.round, k_link)

        with jax.named_scope("fed.broadcast"):
            starts = algorithm.client_start(
                state.algo_state, state.server, state.clients)

        run = partial(local_steps, loss_fn, optimizer, s=s)
        with jax.named_scope("fed.local_train"):
            x_star, opt_state, losses = jax.vmap(
                run, spmd_axis_name=spmd_axis_name)(
                starts, state.opt_state, batches)
        if gather_updates is not None:
            x_star, losses = gather_updates((x_star, losses))

        with jax.named_scope("fed.aggregate"):
            algo_state, server, clients = algorithm.aggregate(
                state.algo_state, state.server, state.clients, x_star, active,
                p_t, state.round)

        last_active = jnp.where(active, state.round, state.last_active)
        new_state = FedState(
            server=server, clients=clients, opt_state=opt_state,
            algo_state=algo_state, link_state=link_state,
            round=state.round + 1, key=key, last_active=last_active,
            buffer=state.buffer)
        metrics = {
            "loss": losses.mean(),
            "num_active": active.sum(),
            "active": active,
            "staleness": (state.round - state.last_active).astype(jnp.float32),
        }
        return new_state, metrics

    return round_fn


def _make_scale_round_fn(loss_fn, optimizer, algorithm, link, fed_cfg,
                         spmd_axis_name, algo_id, strategy, cohort_size,
                         gather_updates=None):
    """The cross-device scale round engines (``repro.scale``).

    Dense buffered (``cohort_size is None``): the synchronous round's exact
    data/key/mask protocol, with the server aggregation routed through the
    buffered semi-async fold — in the degenerate commit-every-round
    configuration the trace mirrors the synchronous branches term for term
    (the bit-for-bit pin in ``tests/test_staleness.py``).

    Cohort (``cohort_size=C``): clients are stateless — a ``[C]`` cohort is
    drawn per round, only its batches are sampled (``source.sample_cohort``),
    every sampled client trains from the server model with a fresh
    optimizer, and aggregation is the buffer engine (fusable family) or the
    sparse gather/scatter branches (stateful rules). No ``[m, n_params]``
    client tensor exists anywhere in the round.
    """
    from repro.scale.buffer import buffered_aggregate, knobs_of
    from repro.scale.participation import cohort_arrivals, sample_cohort

    if not isinstance(algorithm, AlgorithmSpec):
        raise ValueError(
            "the buffered/cohort round engine needs an AlgorithmSpec (got "
            f"{type(algorithm).__name__}; bind algo_id via the algo_id "
            "argument instead)")
    spec = algorithm
    m = fed_cfg.num_clients
    buffered = spec.fusable  # stateful rules take the sparse cohort path
    if strategy is not None and not buffered:
        raise ValueError(
            f"buffered strategies cover the empty-state family only; "
            f"{spec.names} keeps per-client state (use the synchronous or "
            "cohort path)")
    knobs = knobs_of(strategy)
    if buffered:
        op, is_pbc = spec.fused_op(algo_id)
    bound = as_algorithm(spec, algo_id)
    run = partial(local_steps, loss_fn, optimizer, s=fed_cfg.local_steps)

    def commit_clients(commit, in_buffer, server, x_star):
        """Postponed broadcast at commit time: fedpbc's new global model
        reaches exactly the buffered contributors; other members broadcast
        to every row present. Between commits nobody moves."""
        if isinstance(is_pbc, bool):
            bcast = in_buffer if is_pbc else jnp.ones_like(in_buffer)
        else:
            bcast = jnp.where(is_pbc, in_buffer, jnp.ones_like(in_buffer))
        committed = bcast_where(bcast, server, x_star)
        return jax.tree.map(
            lambda c, x: jnp.where(commit, c, x), committed, x_star)

    if cohort_size is None:
        def round_fn(state: FedState, batches) -> tuple:
            key, k_link = jax.random.split(state.key)
            with jax.named_scope("fed.link"):
                active, p_t, link_state = link.sample(
                    state.link_state, state.round, k_link)
            with jax.named_scope("fed.broadcast"):
                starts = bound.client_start(
                    state.algo_state, state.server, state.clients)
            with jax.named_scope("fed.local_train"):
                x_star, opt_state, losses = jax.vmap(
                    run, spmd_axis_name=spmd_axis_name)(
                    starts, state.opt_state, batches)
            if gather_updates is not None:
                x_star, losses = gather_updates((x_star, losses))
            with jax.named_scope("fed.aggregate"):
                in_buffer = state.buffer.in_buffer | active
                buf, server, commit, bmets = buffered_aggregate(
                    state.buffer, state.server, x_star, active, p_t, knobs,
                    op=op, m_total=m, in_buffer_new=in_buffer)
                clients = commit_clients(commit, in_buffer, server, x_star)
            last_active = jnp.where(active, state.round, state.last_active)
            new_state = FedState(
                server=server, clients=clients, opt_state=opt_state,
                algo_state=state.algo_state, link_state=link_state,
                round=state.round + 1, key=key, last_active=last_active,
                buffer=buf)
            metrics = {
                "loss": losses.mean(),
                "num_active": active.sum(),
                "active": active,
                "staleness": (state.round
                              - state.last_active).astype(jnp.float32),
                **bmets,
            }
            return new_state, metrics

        return round_fn

    C = cohort_size

    def round_fn(state: FedState, ds_state, k_data, source) -> tuple:
        key, k_link, k_cohort = jax.random.split(state.key, 3)
        # the link advances over the FULL population (Markov chains etc.
        # keep their dense-time semantics); the cohort sees its gather
        with jax.named_scope("fed.link"):
            active_m, p_t_m, link_state = link.sample(
                state.link_state, state.round, k_link)
            cohort = sample_cohort(k_cohort, m, C)
            c_active, c_p = cohort_arrivals(cohort, active_m, p_t_m)
        with jax.named_scope("fed.sample"):
            batches, ds_state = source.sample_cohort(
                ds_state, state.round, k_data, cohort)
        with jax.named_scope("fed.broadcast"):
            starts = _tile(state.server, C)
            opt_state = jax.vmap(optimizer.init)(starts)
        with jax.named_scope("fed.local_train"):
            x_star, _, losses = jax.vmap(run, spmd_axis_name=spmd_axis_name)(
                starts, opt_state, batches)
        if gather_updates is not None:
            x_star, losses = gather_updates((x_star, losses))
        with jax.named_scope("fed.aggregate"):
            if buffered:
                in_buffer = state.buffer.in_buffer.at[cohort].set(
                    state.buffer.in_buffer[cohort] | c_active)
                buf, server, commit, bmets = buffered_aggregate(
                    state.buffer, state.server, x_star, c_active, c_p, knobs,
                    op=op, m_total=C, in_buffer_new=in_buffer)
                algo_state = state.algo_state
            else:
                algo_state, server = spec.aggregate_cohort(
                    algo_id, state.algo_state, state.server, x_star, cohort,
                    c_active, c_p, state.round)
                buf = state.buffer
                bmets = {"commit": jnp.float32(1.0),
                         "buffer_fill": c_active.sum().astype(jnp.float32),
                         "commit_staleness": jnp.float32(0.0)}
        last_active = state.last_active.at[cohort].set(
            jnp.where(c_active, state.round, state.last_active[cohort]))
        new_state = FedState(
            server=server, clients=(), opt_state=(),
            algo_state=algo_state, link_state=link_state,
            round=state.round + 1, key=key, last_active=last_active,
            buffer=buf)
        metrics = {
            "loss": losses.mean(),
            "num_active": c_active.sum(),
            "active": c_active,
            "staleness": (state.round
                          - state.last_active).astype(jnp.float32),
            **bmets,
        }
        return new_state, ds_state, metrics

    round_fn.needs_source = True
    return round_fn


# ---------------------------------------------------------------------------
# Multi-round scan engine
# ---------------------------------------------------------------------------

# Metrics stacked per round by run_rounds. "active" ([K, m] bool) is cheap but
# redundant with staleness for most consumers; callers opt in via metric_keys.
DEFAULT_METRIC_KEYS = ("loss", "num_active", "staleness")


def make_round_step(round_fn, source):
    """One (sample batch -> run round) step over a ``DataSource``.

    The per-round data key is ``fold_in(data_key, state.round)`` — a pure
    function of the carried round counter — so the scanned engine and a
    sequential Python loop over this very function draw identical batches.
    Returns ``step(state, ds_state, data_key) -> (state, ds_state, metrics)``.
    """

    if getattr(round_fn, "needs_source", False):
        # cohort engine: the round draws its own cohort and samples only
        # that cohort's batches, so it needs the source inside; the source
        # capability check belongs here, at build time, not in the traced
        # round body
        if source.sample_cohort is None:
            raise ValueError(
                f"cohort mode needs a DataSource with sample_cohort "
                f"(source {source.name!r} has none)")

        def step(state: FedState, ds_state, data_key):
            k_data = jax.random.fold_in(data_key, state.round)
            return round_fn(state, ds_state, k_data, source)

        return step

    def step(state: FedState, ds_state, data_key):
        k_data = jax.random.fold_in(data_key, state.round)
        with jax.named_scope("fed.sample"):
            batches, ds_state = source.sample(ds_state, state.round, k_data)
        state, metrics = round_fn(state, batches)
        return state, ds_state, metrics

    return step


def make_run_rounds(loss_fn: Callable, optimizer, algorithm,
                    link: LinkProcess, fed_cfg: FederationConfig, source,
                    spmd_axis_name: Optional[str] = None,
                    metric_keys=DEFAULT_METRIC_KEYS,
                    donate: Optional[bool] = None,
                    algo_id=0, use_kernel: bool = False,
                    strategy=None, cohort_size: Optional[int] = None):
    """Build the scanned multi-round entry point.

    ``algorithm`` may be an ``AlgorithmSpec`` table bound at ``algo_id``
    with the aggregation path picked by ``use_kernel`` (see
    ``make_round_fn``). ``strategy``/``cohort_size`` select the
    cross-device scale engines (``repro.scale``; see ``make_round_fn``) —
    in those modes the state must come from ``init_fed_state`` with the
    matching ``buffered``/``stateless_clients`` flags.

    Returns ``run_rounds(state, ds_state, data_key, num_rounds)`` →
    ``(state', ds_state', metrics)`` where every entry of ``metrics`` is a
    device array with a leading ``[num_rounds]`` axis (e.g. ``loss [K]``,
    ``staleness [K, m]``). ``num_rounds`` is static (one compile per distinct
    chunk length); ``state``/``ds_state`` buffers are donated on backends that
    support donation, so chunked callers can loop
    ``state, ds_state, mets = run_rounds(state, ds_state, key, chunk)``
    without doubling peak memory.
    """
    round_fn = make_round_fn(loss_fn, optimizer, algorithm, link, fed_cfg,
                             spmd_axis_name, algo_id=algo_id,
                             use_kernel=use_kernel, strategy=strategy,
                             cohort_size=cohort_size)
    step = make_round_step(round_fn, source)
    if donate is None:
        donate = jax.default_backend() != "cpu"  # CPU ignores donation noisily

    def run_rounds(state: FedState, ds_state, data_key, num_rounds: int):
        def body(carry, _):
            st, ds = carry
            st, ds, metrics = step(st, ds, data_key)
            return (st, ds), {k: metrics[k] for k in metric_keys}

        # unroll=1 always: num_rounds can be in the thousands, and the
        # analysis-mode full unroll (repro.models.flags) is for layer stacks,
        # not the round loop.
        (state, ds_state), metrics = jax.lax.scan(
            body, (state, ds_state), None, length=num_rounds)
        return state, ds_state, metrics

    return jax.jit(run_rounds, static_argnums=(3,),
                   donate_argnums=(0, 1) if donate else ())


def run_rounds_loop(state: FedState, ds_state, data_key, num_rounds: int, *,
                    round_fn, source, metric_keys=DEFAULT_METRIC_KEYS,
                    step=None):
    """Sequential reference: the SAME step as the scanned engine, dispatched
    once per round from Python. Used by the equivalence tests and as the
    baseline of ``benchmarks/throughput.py``; prefer ``make_run_rounds`` for
    real work.

    ``step``: pass a prebuilt ``jax.jit(make_round_step(round_fn, source))``
    to reuse its compile cache across calls (each default-built closure gets
    its own cache entry)."""
    if step is None:
        step = jax.jit(make_round_step(round_fn, source))
    collected = []
    for _ in range(num_rounds):
        state, ds_state, metrics = step(state, ds_state, data_key)
        collected.append({k: metrics[k] for k in metric_keys})
    if collected:
        stacked = {k: jnp.stack([m[k] for m in collected]) for k in metric_keys}
    else:
        # match the scanned engine: a [0, ...] leading axis on every metric's
        # true per-round shape (e.g. staleness [0, m]), not a bare [0]
        shapes = jax.eval_shape(step, state, ds_state, data_key)[2]
        stacked = {k: jnp.zeros((0,) + shapes[k].shape, shapes[k].dtype)
                   for k in metric_keys}
    return state, ds_state, stacked


jax.tree_util.register_dataclass(
    FedState,
    data_fields=["server", "clients", "opt_state", "algo_state", "link_state",
                 "round", "key", "last_active", "buffer"],
    meta_fields=[],
)
