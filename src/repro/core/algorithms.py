"""Federated aggregation algorithms over arbitrary parameter pytrees.

The algorithm layer is **data, not closures**: every aggregation rule is one
entry of a per-family table inside an :class:`AlgorithmSpec`, and behavior is
selected by an ``algo_id`` that may be a *traced* per-trajectory input. Two
pure functions act on a ``FedState`` whose client-indexed leaves carry a
leading ``[m, ...]`` axis:

- ``client_start(algo_id, algo_state, server, clients) -> [m, ...] start
  params`` (what each client trains from this round) — a branchless select
  between "resume from your own model" (FedPBC's implicit gossiping) and
  "broadcast the server model";
- ``aggregate(algo_id, algo_state, server, clients, x_star, active, p_t, t)
  -> (algo_state, server', clients')`` — a ``lax.switch`` over the family's
  branch table (server update + postponed/instant broadcast semantics).

All per-algorithm state lives in ONE superset container, :class:`AlgoState`:
FedAU's inter-participation gap stats, MIFA's per-client update memory,
F3AST's availability rates, FedPBC-M's server momentum. Leaves a family never
uses are **zero-sized** (leading axis 0 — no storage, stable pytree
structure); leaves only *some* members use are materialized for the whole
family and simply passed through untouched by the others (masked). Because
the state is a plain pytree selected by data, a whole state-compatible family
(e.g. fedavg / fedavg_all / fedavg_known_p / fedpbc, all with empty state)
runs as ONE compiled program over a batched ``algo_id`` — the sweep engine's
algorithm axis (``repro.experiments``).

FedPBC (the paper, Alg. 1): clients always start from their *own* model
(implicit gossiping); the server averages the active clients' models and
broadcasts the average back **only to the active clients** — the postponed
broadcast. The resulting mixing matrix is Eq. (4).

Baselines: FedAvg, FedAvg-all, FedAU, MIFA, FedAvg-known-p, F3AST
(§7.2, "Baseline algorithms").

The legacy single-algorithm :class:`Algorithm` interface (``make_algorithm``,
the per-name factories) is preserved: it binds a one-member spec with a
*static* ``algo_id``, which dispatches directly to the branch — the same
trace as the historical closures, so existing callers and their bit-for-bit
guarantees are untouched.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, FrozenSet, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FederationConfig

Pytree = Any


def _bmask(mask, leaf):
    """Broadcast [m] mask against an [m, ...] leaf."""
    return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)


def _f32(x):
    return x.astype(jnp.float32)


def masked_mean(xs: Pytree, active) -> Pytree:
    """Mean over the client axis restricted to active clients.

    = (1/|A|) sum_{i in A} x_i ; falls back to 0 when A is empty (callers
    guard with ``any_active``). This is the paper's server aggregation and
    the op kernelized in ``repro.kernels.masked_agg``.

    Summed, divided and returned in float32 whatever the leaves' dtype: a
    mean of a few clients' bfloat16 parameters rounded back to the
    bfloat16 grid would move updates of about one ulp by as much as the
    updates themselves.
    """
    denom = jnp.maximum(active.sum().astype(jnp.float32), 1.0)
    return jax.tree.map(
        lambda x: (_f32(x) * _bmask(active, _f32(x))).sum(0) / denom, xs)


def weighted_sum(xs: Pytree, w) -> Pytree:
    """``sum_i w_i x_i`` over the client axis, in float32."""
    return jax.tree.map(lambda x: (_f32(x) * _bmask(w, _f32(x))).sum(0), xs)


def bcast_where(active, new: Pytree, old: Pytree) -> Pytree:
    """Per-client select: active clients receive ``new``, others keep ``old``."""
    return jax.tree.map(
        lambda n, o: jnp.where(_bmask(active, o) > 0, jnp.broadcast_to(n, o.shape), o),
        new, old)


@dataclass(frozen=True)
class Algorithm:
    """A spec bound to one (static or traced) ``algo_id`` — the historical
    single-algorithm interface every sequential caller uses."""

    name: str
    init: Callable[[Pytree, int], Pytree]
    client_start: Callable[..., Pytree]
    aggregate: Callable[..., tuple]
    needs_p: bool = False


def _tile(server: Pytree, m: int) -> Pytree:
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (m,) + x.shape).copy(), server)


# ---------------------------------------------------------------------------
# The unified algorithm state: one superset container for every rule's needs.
# ---------------------------------------------------------------------------


@dataclass
class AlgoState:
    """Superset per-algorithm state. Fields a family does not need are
    zero-sized (leading axis 0); fields only some members need are full-sized
    and inert for the others. ``mem``/``mom`` mirror the server params pytree
    with a leading client (m) / singleton (1) axis respectively."""

    gap: Pytree        # [m] rounds since last active (FedAU), or [0]
    sum_gaps: Pytree   # [m] accumulated gaps (FedAU), or [0]
    n_gaps: Pytree     # [m] gap counts (FedAU), or [0]
    lam: Pytree        # [m] availability EMA (F3AST), or [0]
    mem: Pytree        # [m, ...] last normalized updates (MIFA), or [0, ...]
    mom: Pytree        # [1, ...] server momentum (FedPBC-M), or [0, ...]


jax.tree_util.register_dataclass(
    AlgoState,
    data_fields=["gap", "sum_gaps", "n_gaps", "lam", "mem", "mom"],
    meta_fields=[],
)


# ---------------------------------------------------------------------------
# Branch table: one aggregate function per rule, all over the unified state.
# Each branch must preserve the state's structure/shapes (lax.switch needs
# identical output signatures across a family) — untouched fields pass
# through bitwise.
# ---------------------------------------------------------------------------


def _agg_fedpbc(algo, server, clients, x_star, active, p_t, t):
    """FedPBC (Alg. 1): masked mean over active clients; postponed broadcast."""
    any_active = active.any()
    agg = masked_mean(x_star, active)
    new_server = jax.tree.map(
        lambda a, s: jnp.where(any_active, a.astype(s.dtype), s), agg, server)
    # postponed broadcast: only active clients receive the new global model
    new_clients = bcast_where(active, new_server, x_star)
    return algo, new_server, new_clients


def _agg_fedavg(algo, server, clients, x_star, active, p_t, t):
    """Vanilla FedAvg: average active clients; broadcast to everyone."""
    any_active = active.any()
    agg = masked_mean(x_star, active)
    new_server = jax.tree.map(
        lambda a, s: jnp.where(any_active, a.astype(s.dtype), s), agg, server)
    m = active.shape[0]
    return algo, new_server, _tile(new_server, m)


def _agg_fedavg_all(algo, server, clients, x_star, active, p_t, t):
    """FedAvg-all: average over ALL m clients; inactive contribute zero."""
    m = active.shape[0]
    w = active.astype(jnp.float32) / m
    delta = jax.tree.map(lambda xs, s: xs.astype(jnp.float32) - s[None].astype(jnp.float32), x_star, server)
    upd = weighted_sum(delta, w)
    new_server = jax.tree.map(lambda s, u: s + u.astype(s.dtype), server, upd)
    return algo, new_server, _tile(new_server, m)


def _agg_fedavg_known_p(algo, server, clients, x_star, active, p_t, t):
    """FedAvg with known p_i^t: active updates importance-weighted by 1/p_i^t."""
    m = active.shape[0]
    w = active.astype(jnp.float32) / jnp.maximum(p_t, 1e-3) / m
    delta = jax.tree.map(lambda xs, s: xs.astype(jnp.float32) - s[None].astype(jnp.float32), x_star, server)
    upd = weighted_sum(delta, w)
    new_server = jax.tree.map(lambda s, u: s + u.astype(s.dtype), server, upd)
    return algo, new_server, _tile(new_server, m)


def _make_agg_fedau(K: int):
    """FedAU (Wang & Ji 2023): online participation estimate via mean
    inter-participation gap, capped at K."""

    def branch(algo, server, clients, x_star, active, p_t, t):
        m = active.shape[0]
        gap = jnp.minimum(algo.gap + 1.0, float(K))
        sum_gaps = algo.sum_gaps + jnp.where(active, gap, 0.0)
        n_gaps = algo.n_gaps + active.astype(jnp.float32)
        mean_gap = jnp.where(n_gaps > 0, sum_gaps / jnp.maximum(n_gaps, 1.0), 1.0)
        w = active.astype(jnp.float32) * mean_gap / m   # mean gap ~= 1/p_i
        delta = jax.tree.map(lambda xs, s: xs.astype(jnp.float32) - s[None].astype(jnp.float32), x_star, server)
        upd = weighted_sum(delta, w)
        new_server = jax.tree.map(lambda s, u: s + u.astype(s.dtype), server, upd)
        new_algo = dataclasses.replace(
            algo, gap=jnp.where(active, 0.0, gap), sum_gaps=sum_gaps,
            n_gaps=n_gaps)
        return new_algo, new_server, _tile(new_server, m)

    return branch


def _agg_mifa(algo, server, clients, x_star, active, p_t, t):
    """MIFA (Gu et al. 2021): memory of every client's last normalized update."""
    m = active.shape[0]
    delta = jax.tree.map(lambda xs, s: xs.astype(jnp.float32) - s[None].astype(jnp.float32), x_star, server)
    mem = jax.tree.map(
        lambda old, new: jnp.where(_bmask(active, old) > 0, new.astype(old.dtype), old),
        algo.mem, delta)
    upd = jax.tree.map(lambda g: g.mean(0), mem)
    new_server = jax.tree.map(lambda s, u: s + u.astype(s.dtype), server, upd)
    return dataclasses.replace(algo, mem=mem), new_server, _tile(new_server, m)


def _make_agg_f3ast(beta: float, cap: int):
    """F3AST (Ribero et al. 2022): availability-balanced scheduling — keep at
    most ``cap`` active clients with the SMALLEST availability EMA lambda_i."""

    def branch(algo, server, clients, x_star, active, p_t, t):
        lam = (1.0 - beta) * algo.lam + beta * active.astype(jnp.float32)
        # rank active clients by lambda ascending; keep `cap`
        score = jnp.where(active, lam, jnp.inf)
        order = jnp.argsort(score)
        rank = jnp.argsort(order)
        selected = active & (rank < cap)
        any_sel = selected.any()
        agg = masked_mean(x_star, selected)
        new_server = jax.tree.map(
            lambda a, s: jnp.where(any_sel, a.astype(s.dtype), s), agg, server)
        m = active.shape[0]
        return dataclasses.replace(algo, lam=lam), new_server, _tile(new_server, m)

    return branch


def _make_agg_fedpbc_m(beta: float):
    """FedPBC-M (beyond-paper): FedPBC + server momentum on the aggregated
    direction. The postponed-broadcast/gossip structure is unchanged (the
    momentum acts on x^{t+1} - x^t, which Thm. 1's descent lemma controls);
    empirically it accelerates the information-mixing phase under sparse
    participation. Recorded as an EXTENSION, not part of the reproduction."""

    def branch(algo, server, clients, x_star, active, p_t, t):
        any_active = active.any()
        agg = masked_mean(x_star, active)
        step = jax.tree.map(
            lambda a, s: jnp.where(any_active, a.astype(jnp.float32)
                                   - s.astype(jnp.float32), 0.0), agg, server)
        mom = jax.tree.map(lambda m_, g: beta * m_[0] + g, algo.mom, step)
        new_server = jax.tree.map(
            lambda s, m_: (s.astype(jnp.float32) + m_).astype(s.dtype), server, mom)
        new_clients = bcast_where(active, new_server, x_star)
        new_algo = dataclasses.replace(
            algo, mom=jax.tree.map(lambda x: x[None], mom))
        return new_algo, new_server, new_clients

    return branch


@dataclass(frozen=True)
class _AlgoDef:
    """Registry row: which AlgoState fields a rule materializes, where its
    clients start from, whether it consumes p_i^t, and its branch factory
    (knobs -> aggregate function)."""

    needs: FrozenSet[str]
    from_clients: bool
    needs_p: bool
    make_branch: Callable[["AlgorithmSpec"], Callable]


_DEFS: Dict[str, _AlgoDef] = {
    "fedpbc": _AlgoDef(frozenset(), True, False, lambda spec: _agg_fedpbc),
    "fedpbc_m": _AlgoDef(frozenset({"mom"}), True, False,
                         lambda spec: _make_agg_fedpbc_m(spec.fedpbc_m_beta)),
    "fedavg": _AlgoDef(frozenset(), False, False, lambda spec: _agg_fedavg),
    "fedavg_all": _AlgoDef(frozenset(), False, False,
                           lambda spec: _agg_fedavg_all),
    "fedau": _AlgoDef(frozenset({"gap", "sum_gaps", "n_gaps"}), False, False,
                      lambda spec: _make_agg_fedau(spec.fedau_K)),
    "mifa": _AlgoDef(frozenset({"mem"}), False, False, lambda spec: _agg_mifa),
    "fedavg_known_p": _AlgoDef(frozenset(), False, True,
                               lambda spec: _agg_fedavg_known_p),
    "f3ast": _AlgoDef(frozenset({"lam"}), False, False,
                      lambda spec: _make_agg_f3ast(spec.f3ast_beta,
                                                   spec.f3ast_cap)),
}


def state_signature(name: str) -> FrozenSet[str]:
    """The AlgoState fields ``name`` materializes — its batching-compatibility
    class. Algorithms with equal signatures share state shapes and batch into
    one compiled program."""
    if name not in _DEFS:
        raise ValueError(
            f"unknown algorithm {name!r}; available: {sorted(_DEFS)}")
    return _DEFS[name].needs


def algo_family(name: str) -> Tuple[str, ...]:
    """The canonical state-compatible family containing ``name``: every
    registered algorithm with the same state signature, in registry order.
    ``algo_id`` values index this tuple, and the executor keys its runner
    cache on it — so any subset of a family shares one compiled program."""
    sig = state_signature(name)
    return tuple(n for n in _DEFS if _DEFS[n].needs == sig)


def _is_static(algo_id) -> bool:
    return isinstance(algo_id, (int, np.integer))


@dataclass(frozen=True)
class AlgorithmSpec:
    """A family of aggregation rules as data: member ``names`` (indexed by
    ``algo_id``) plus their static knobs. ``client_start``/``aggregate`` are
    implemented ONCE over the branch table — with a static (python int)
    ``algo_id`` they dispatch directly (the historical per-algorithm trace);
    with a traced ``algo_id`` they lower to a branchless select /
    ``lax.switch``, which under ``vmap`` evaluates every branch and selects
    per trajectory, so one program serves the whole family."""

    names: Tuple[str, ...]
    fedau_K: int = 50
    f3ast_beta: float = 0.01
    f3ast_cap: int = 10
    fedpbc_m_beta: float = 0.8

    def __post_init__(self):
        if not self.names:
            raise ValueError("AlgorithmSpec.names must be non-empty")
        unknown = [n for n in self.names if n not in _DEFS]
        if unknown:
            raise ValueError(
                f"AlgorithmSpec.names contains unknown algorithms {unknown}; "
                f"available: {sorted(_DEFS)}")
        if len(set(self.names)) != len(self.names):
            raise ValueError(
                f"AlgorithmSpec.names contains duplicates: {self.names}")

    @property
    def needs(self) -> FrozenSet[str]:
        out: FrozenSet[str] = frozenset()
        for n in self.names:
            out = out | _DEFS[n].needs
        return out

    @property
    def needs_p(self) -> bool:
        return any(_DEFS[n].needs_p for n in self.names)

    def id_of(self, name: str) -> int:
        """Index of ``name`` in this spec's table (the value an ``algo_id``
        input must carry to select it)."""
        if name not in self.names:
            raise ValueError(f"{name!r} is not in this spec's family "
                             f"{self.names}")
        return self.names.index(name)

    # -- the two per-round primitives, implemented once over the table -----

    def init(self, server: Pytree, m: int) -> AlgoState:
        """The family's unified state: needed fields at full size, the rest
        zero-sized (leading axis 0)."""
        u = self.needs

        def vec(field, fill=0.0):
            n = m if field in u else 0
            return jnp.full((n,), fill, jnp.float32)

        mem_m = m if "mem" in u else 0
        mom_m = 1 if "mom" in u else 0
        return AlgoState(
            gap=vec("gap"), sum_gaps=vec("sum_gaps"), n_gaps=vec("n_gaps"),
            lam=vec("lam", 0.5),
            mem=jax.tree.map(
                lambda x: jnp.zeros((mem_m,) + x.shape, x.dtype), server),
            mom=jax.tree.map(
                lambda x: jnp.zeros((mom_m,) + x.shape, jnp.float32), server),
        )

    def client_start(self, algo_id, algo_state, server: Pytree,
                     clients: Pytree) -> Pytree:
        m = jax.tree.leaves(clients)[0].shape[0]
        if _is_static(algo_id) or len(self.names) == 1:
            idx = int(algo_id) if _is_static(algo_id) else 0
            return clients if _DEFS[self.names[idx]].from_clients \
                else _tile(server, m)
        from_clients = jnp.asarray(
            [_DEFS[n].from_clients for n in self.names])[algo_id]
        tiled = _tile(server, m)
        return jax.tree.map(
            lambda c, s: jnp.where(from_clients, c, s), clients, tiled)

    @property
    def fusable(self) -> bool:
        """Whether every member's aggregation folds into the fused Pallas
        kernel's branch select (``repro.kernels.dispatch.FUSED_OPS``) — the
        empty-state family. Stateful rules keep the ``lax.switch`` path."""
        from repro.kernels.dispatch import FUSED_OPS
        return all(n in FUSED_OPS for n in self.names)

    def fused_op(self, algo_id) -> tuple:
        """The fused-branch selectors for a fusable family: ``(op, is_pbc)``
        where ``op`` is the member's aggregation opcode
        (``repro.kernels.dispatch.FUSED_OPS``) and ``is_pbc`` marks the
        postponed-broadcast member. Python scalars for a static ``algo_id``,
        traced selects otherwise — the shared dispatch of the fused kernel
        path and the buffered engine (``repro.scale.buffer``)."""
        from repro.kernels.dispatch import FUSED_OPS

        if _is_static(algo_id):
            name = self.names[int(algo_id)]
            return FUSED_OPS[name], name == "fedpbc"
        op = jnp.asarray([FUSED_OPS[n] for n in self.names],
                         jnp.int32)[algo_id]
        is_pbc = jnp.asarray([n == "fedpbc" for n in self.names])[algo_id]
        return op, is_pbc

    def aggregate_cohort(self, algo_id, algo_state, server, x_star, cohort,
                         c_active, c_p, t) -> tuple:
        """Sparse cohort aggregation for a stateful rule: per-client state
        rows are gathered/scattered at ``cohort`` only
        (``repro.scale.sparse_state``), so the round touches O(C) state.
        Stateful families are singletons (unique state signatures), so
        dispatch is always static. Returns ``(algo_state', server')``."""
        from repro.scale.sparse_state import cohort_branch

        if not (_is_static(algo_id) or len(self.names) == 1):
            raise ValueError(
                "cohort aggregation needs a static algo_id (stateful "
                f"families are singletons; got a traced id over {self.names})")
        idx = int(algo_id) if _is_static(algo_id) else 0
        branch = cohort_branch(self.names[idx], self)
        return branch(algo_state, server, x_star, cohort, c_active, c_p, t)

    def aggregate(self, algo_id, algo_state, server, clients, x_star, active,
                  p_t, t, use_kernel: bool = False) -> tuple:
        if use_kernel and self.fusable:
            return self._aggregate_fused(algo_id, algo_state, server,
                                         x_star, active, p_t)
        branches = [_DEFS[n].make_branch(self) for n in self.names]
        if _is_static(algo_id) or len(self.names) == 1:
            idx = int(algo_id) if _is_static(algo_id) else 0
            return branches[idx](algo_state, server, clients, x_star, active,
                                 p_t, t)
        return jax.lax.switch(algo_id, branches, algo_state, server, clients,
                              x_star, active, p_t, t)

    def _aggregate_fused(self, algo_id, algo_state, server, x_star, active,
                         p_t) -> tuple:
        """The fused-kernel aggregate: one backend-dispatched pass per leaf
        computes the new server params with the family's weighting branches
        selected by a (possibly traced) opcode INSIDE the kernel body, then
        one select updates the clients (postponed broadcast for fedpbc,
        instant for the FedAvg variants). Subsumes the ``lax.switch`` that
        evaluates every branch under vmap; the family's ``algo_state`` is
        empty and passes through untouched."""
        from repro.kernels.dispatch import fused_agg_pytree

        op, is_pbc = self.fused_op(algo_id)
        if _is_static(algo_id):
            bcast = active if is_pbc else jnp.ones_like(active)
        else:
            bcast = active | ~is_pbc
        new_server = fused_agg_pytree(x_star, active, op, server, p_t)
        # fedpbc: only active clients receive the new global model (the
        # postponed broadcast); every other member broadcasts to all m —
        # the all-ones mask makes bcast_where coincide with _tile.
        new_clients = bcast_where(bcast, new_server, x_star)
        return algo_state, new_server, new_clients

    def bind(self, algo_id: Union[int, jnp.ndarray] = 0,
             use_kernel: bool = False) -> Algorithm:
        """Fix the dispatch index and expose the historical ``Algorithm``
        interface. A python-int ``algo_id`` yields the exact per-algorithm
        trace; a traced one yields the family switch. ``use_kernel`` routes
        a fusable family's aggregation through the backend-dispatched fused
        kernel (``repro.kernels.dispatch``) instead of the XLA switch."""
        if _is_static(algo_id):
            name = self.names[int(algo_id)]
            needs_p = _DEFS[name].needs_p
        else:
            name = "+".join(self.names)
            needs_p = self.needs_p
        return Algorithm(
            name=name,
            init=self.init,
            client_start=lambda a, s, c: self.client_start(algo_id, a, s, c),
            aggregate=lambda a, s, c, xs, act, p, t: self.aggregate(
                algo_id, a, s, c, xs, act, p, t, use_kernel=use_kernel),
            needs_p=needs_p)


def as_algorithm(algorithm: Union[Algorithm, AlgorithmSpec],
                 algo_id=0, use_kernel: bool = False) -> Algorithm:
    """Normalize an ``Algorithm | AlgorithmSpec`` argument: specs are bound at
    ``algo_id`` (with the aggregation path picked by ``use_kernel``),
    algorithms pass through (their dispatch is already fixed)."""
    if isinstance(algorithm, AlgorithmSpec):
        return algorithm.bind(algo_id, use_kernel=use_kernel)
    return algorithm


# ---------------------------------------------------------------------------
# Single-algorithm factories (the historical constructors)
# ---------------------------------------------------------------------------


def fedpbc() -> Algorithm:
    return AlgorithmSpec(("fedpbc",)).bind(0)


def fedavg() -> Algorithm:
    return AlgorithmSpec(("fedavg",)).bind(0)


def fedavg_all() -> Algorithm:
    return AlgorithmSpec(("fedavg_all",)).bind(0)


def fedavg_known_p() -> Algorithm:
    return AlgorithmSpec(("fedavg_known_p",)).bind(0)


def fedau(K: int = 50) -> Algorithm:
    return AlgorithmSpec(("fedau",), fedau_K=K).bind(0)


def mifa() -> Algorithm:
    return AlgorithmSpec(("mifa",)).bind(0)


def f3ast(beta: float = 0.01, cap: int = 10) -> Algorithm:
    return AlgorithmSpec(("f3ast",), f3ast_beta=beta, f3ast_cap=cap).bind(0)


def fedpbc_m(beta: float = 0.8) -> Algorithm:
    return AlgorithmSpec(("fedpbc_m",), fedpbc_m_beta=beta).bind(0)


ALGORITHMS = {
    "fedpbc": fedpbc,
    "fedpbc_m": fedpbc_m,
    "fedavg": fedavg,
    "fedavg_all": fedavg_all,
    "fedau": fedau,
    "mifa": mifa,
    "fedavg_known_p": fedavg_known_p,
    "f3ast": f3ast,
}


def make_algorithm_spec(names: Tuple[str, ...],
                        cfg: FederationConfig = None) -> AlgorithmSpec:
    """Spec table for a family, with static knobs drawn from ``cfg``."""
    kw = {} if cfg is None else dict(
        fedau_K=cfg.fedau_K, f3ast_beta=cfg.f3ast_beta, f3ast_cap=cfg.f3ast_cap)
    return AlgorithmSpec(tuple(names), **kw)


def make_algorithm(cfg: FederationConfig) -> Algorithm:
    return make_algorithm_spec((cfg.algorithm,), cfg).bind(0)
