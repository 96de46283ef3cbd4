"""Declarative sweep grids: ``SweepSpec`` -> batched device simulations.

A paper evaluation is a grid of ``(algorithm x unreliable-link scheme x
hyperparameter point x seed)`` cells. The executor walks only the *algorithm
family x scheme* axes in Python — distinct families / schemes carry distinct
``algo_state`` / ``link_state`` pytree shapes and branch tables, so they are
necessarily separate compiles — and collapses EVERY other swept axis inside
one compiled program per family cell
(``repro.experiments.sweep.make_batched_run_rounds``): the *algorithm* axis
(a traced per-trajectory ``algo_id`` into an ``AlgorithmSpec`` table) and the
hyperparameter axes (``lrs x gammas x alphas x sigma0s x deltas``) are
flattened with the seed axis into a single leading batch dimension.

Algorithms batch together when they are *state-compatible* —
``repro.core.algo_family`` groups them by the set of unified-state fields
they materialize, e.g. fedavg / fedavg_all / fedavg_known_p / fedpbc all
carry an empty state and run as ONE program; a mixed grid (say fedpbc +
fedau) falls back to one program per family. The runner cache is keyed by
the family (state structure), never by an individual algorithm name, so
sweeping any subset of a family reuses one compile.

Nothing swept is a compile-time constant: the algorithm is a traced index,
lr and gamma/period are traced scalars consumed by factories inside the
trace, sigma0/delta (and alpha's effect on connectivity) only shape the
traced per-trajectory ``p_base`` input, alpha's Dirichlet re-partition
travels as the traced ``ds_state`` index table, and the dataset arrays
themselves are traced ``shared`` inputs. Compiled runners are memoized in a
module-level cache whose key is therefore *structure-only* — e.g. the fig-8
alpha/gamma/delta/sigma0 ablations, an LR search, and a FedPBC-vs-baselines
comparison all reuse ONE compile per (family, scheme)
(``tests/test_traced_axes.py`` / ``tests/test_algo_axis.py`` count the
compiles).
"""
from __future__ import annotations

import dataclasses
import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.configs.base import FederationConfig
from repro.core.algorithms import (
    ALGORITHMS,
    algo_family,
    make_algorithm,
    make_algorithm_spec,
)
from repro.core.connectivity import build_base_probs, make_link_process
from repro.kernels.dispatch import FUSED_OPS, resolve_use_kernel
from repro.experiments.results import ResultsStore, buffered_summary, summarize
from repro.scale.buffer import (
    SYNC,
    Strategy,
    strategy_knob_columns,
)
from repro.scale.buffer import BUFFER_METRIC_KEYS as _BUFFER_KEYS
from repro.experiments.shard import (
    AUTO,
    pad_batch,
    resolve_batch_mesh,
    shard_batch,
)
from repro.sharding.specs import replicated_sharding
from repro.experiments.sweep import (
    CellBatch,
    eval_rounds,
    make_batched_run_rounds,
    stack_seed_keys,
)
from repro.experiments.tasks import (
    ClassificationTask,
    TracedClassificationTask,
    make_classification_task,
    make_traced_classification_task,
    make_traced_lm_task,
)
from repro.optim import paper_decay, sgd

# The paper's evaluation grid (§7.2): 7 algorithms x 6 link schemes.
ALGOS = ("fedpbc", "fedavg", "fedavg_all", "fedau", "f3ast",
         "fedavg_known_p", "mifa")

SCHEMES = {
    "bernoulli_ti": dict(scheme="bernoulli", time_varying=False),
    "bernoulli_tv": dict(scheme="bernoulli", time_varying=True),
    "markov_hom": dict(scheme="markov", time_varying=False),
    "markov_nonhom": dict(scheme="markov", time_varying=True),
    "cyclic": dict(scheme="cyclic", cyclic_reset=False),
    "cyclic_reset": dict(scheme="cyclic", cyclic_reset=True),
}

# The swept-inside-one-compile knobs, in flattening order: a hyperparameter
# point is one (lr, gamma, alpha, sigma0, delta) combination.
HPARAM_FIELDS = ("lr", "gamma", "alpha", "sigma0", "delta")


@dataclass(frozen=True)
class SweepSpec:
    """One declarative grid: which cells to run and with what protocol.

    The scalar fields (``lr``, ``gamma``, ``alpha``, ``sigma0``, ``delta``)
    give the default hyperparameter point; the plural axes (``lrs``,
    ``gammas``, ``alphas``, ``sigma0s``, ``deltas``) override them with a
    swept list whose cartesian product is flattened — together with ``seeds``
    (and, within a state-compatible family, ``algorithms``) — into the one
    batch axis of the compiled cell program. An empty hyperparameter axis
    means "use the scalar field".

    Specs are validated at construction: empty ``algorithms``/``schemes``/
    ``seeds`` axes, duplicate entries on any of them, and unknown
    algorithm/scheme names all raise an immediate ``ValueError`` naming the
    offending field, instead of failing deep inside tracing (or silently
    double-counting a row in every mean/CI).
    """

    algorithms: Tuple[str, ...] = ("fedpbc", "fedavg")
    schemes: Tuple[str, ...] = ("bernoulli_ti",)
    seeds: Tuple[int, ...] = (0,)
    rounds: int = 100
    eval_every: int = 25            # <= 0: single eval at the final round
    # federation protocol
    num_clients: int = 100
    local_steps: int = 5
    batch_size: int = 32
    lr: float = 0.1                 # paper_decay base LR
    # Eq.-9 / heterogeneity knobs
    alpha: float = 0.1
    sigma0: float = 10.0
    delta: float = 0.02
    gamma: float = 0.5
    # hyperparameter axes (traced; empty tuple -> the scalar field above)
    lrs: Tuple[float, ...] = ()
    gammas: Tuple[float, ...] = ()
    alphas: Tuple[float, ...] = ()
    sigma0s: Tuple[float, ...] = ()
    deltas: Tuple[float, ...] = ()
    # shared-dataset / model knobs
    data_seed: int = 0
    dim: int = 32
    classes: int = 10
    hidden: int = 64
    n_per_class: int = 600
    n_train: int = 5000
    per_client: int = 64
    # server-aggregation path: True routes fusable families through the
    # backend-dispatched fused Pallas kernel (repro.kernels.dispatch), False
    # keeps the XLA masked-mean switch, None defers to the REPRO_USE_KERNEL
    # env default. Part of the runner-cache key (the two paths are distinct
    # traced programs); results match within the documented per-backend
    # tolerance (bitwise on CPU fp32 — tests/test_kernel_sweep.py).
    use_kernel: Optional[bool] = None
    # cross-device scale axes (repro.scale): the buffered semi-async
    # strategy axis — one more traced batched dimension of the compiled
    # cell program, (SYNC,) is the historical synchronous engine — and the
    # per-round cohort size C (None: all m clients materialize densely)
    strategies: Tuple[Strategy, ...] = (SYNC,)
    cohort_size: Optional[int] = None
    # extra FederationConfig field overrides, applied last (e.g.
    # (("fedau_K", 100), ("period", 20)))
    fed_overrides: Tuple[Tuple[str, Any], ...] = ()
    # workload: "classification" (the paper's Gaussian/MLP stand-in) or "lm"
    # (transformer next-token task, repro.experiments.tasks
    # .make_traced_lm_task). For "lm" the lm_* knobs shape the model/corpus
    # (classes doubles as the number of corpus styles, per_client /
    # local_steps / batch_size keep their meaning), and dim/hidden/
    # n_per_class/n_train are ignored.
    task: str = "classification"
    lm_arch: str = "smollm-135m"
    # True: the CPU-test form, lm_arch cut to lm_d_model x lm_layers in
    # float32; False: lm_arch at its published widths and depth, computing
    # in its config's dtype with each layer recomputed in the backward pass
    # (lm_d_model / lm_layers ignored). Federated state holds float32
    # master weights either way.
    lm_reduced: bool = True
    lm_d_model: int = 64
    lm_layers: int = 2
    lm_seq: int = 32                # training context length
    lm_n_seqs: int = 256            # corpus size (train sequences)
    lm_n_test: int = 64             # held-out eval sequences

    def __post_init__(self):
        if self.task not in ("classification", "lm"):
            raise ValueError(
                f"SweepSpec.task={self.task!r}; expected 'classification' "
                f"or 'lm'")
        for axis in ("algorithms", "schemes", "seeds"):
            vals = getattr(self, axis)
            if not vals:
                raise ValueError(f"SweepSpec.{axis} is empty; give at least "
                                 f"one entry")
            if len(set(vals)) != len(vals):
                dupes = sorted({v for v in vals if vals.count(v) > 1})
                raise ValueError(
                    f"SweepSpec.{axis} contains duplicates {dupes}: each "
                    f"entry is one independent grid coordinate (duplicates "
                    f"would silently double-count rows and every mean/CI)")
        unknown = [a for a in self.algorithms if a not in ALGORITHMS]
        if unknown:
            raise ValueError(
                f"SweepSpec.algorithms contains unknown algorithms "
                f"{unknown}; available: {sorted(ALGORITHMS)}")
        unknown = [s for s in self.schemes if s not in SCHEMES]
        if unknown:
            raise ValueError(
                f"SweepSpec.schemes contains unknown schemes {unknown}; "
                f"available: {sorted(SCHEMES)}")
        if not self.strategies:
            raise ValueError(
                "SweepSpec.strategies is empty; give at least one Strategy "
                "(repro.scale.SYNC is the synchronous default)")
        bad = [s for s in self.strategies if not isinstance(s, Strategy)]
        if bad:
            raise ValueError(
                f"SweepSpec.strategies entries must be repro.scale.Strategy, "
                f"got {[type(s).__name__ for s in bad]}")
        names = [s.name for s in self.strategies]
        if len(set(names)) != len(names):
            dupes = sorted({n for n in names if names.count(n) > 1})
            raise ValueError(
                f"SweepSpec.strategies contains duplicate names {dupes}: "
                f"each strategy is one independent grid coordinate")
        if self.cohort_size is not None \
                and not 1 <= self.cohort_size <= self.num_clients:
            raise ValueError(
                f"SweepSpec.cohort_size={self.cohort_size} must be in "
                f"[1, num_clients={self.num_clients}]")
        pop = self.cohort_size if self.cohort_size is not None \
            else self.num_clients
        for s in self.strategies:
            if not 1 <= s.buffer_size <= pop:
                raise ValueError(
                    f"SweepSpec.strategies[{s.name!r}].buffer_size="
                    f"{s.buffer_size} must be in [1, {pop}] (at most the "
                    f"{'cohort size' if self.cohort_size else 'client count'}"
                    f" — a larger buffer could never fill)")
            if s.deadline_rounds < 1:
                raise ValueError(
                    f"SweepSpec.strategies[{s.name!r}].deadline_rounds="
                    f"{s.deadline_rounds} must be >= 1 (the buffer commits "
                    f"at a round boundary at the earliest)")
            if not 0.0 <= s.staleness_discount < 1.0:
                raise ValueError(
                    f"SweepSpec.strategies[{s.name!r}].staleness_discount="
                    f"{s.staleness_discount} must be in [0, 1)")
        if self.strategies != (SYNC,):
            stateful = [a for a in self.algorithms if a not in FUSED_OPS]
            if stateful:
                raise ValueError(
                    f"SweepSpec.strategies has buffered entries but "
                    f"algorithms {stateful} keep per-client state; buffered "
                    f"semi-async aggregation covers the empty-state family "
                    f"{sorted(FUSED_OPS)} only")

    def hparam_points(self) -> List[Dict[str, float]]:
        """The flattened hyperparameter grid: one dict per point, in
        ``itertools.product`` order over ``HPARAM_FIELDS``."""
        axes = [tuple(getattr(self, f + "s")) or (getattr(self, f),)
                for f in HPARAM_FIELDS]
        return [dict(zip(HPARAM_FIELDS, combo))
                for combo in itertools.product(*axes)]

    def cell_config(self, algo: str, scheme: str) -> FederationConfig:
        if scheme not in SCHEMES:
            raise KeyError(f"unknown scheme {scheme!r}; available: "
                           f"{sorted(SCHEMES)}")
        if algo not in ALGORITHMS:
            raise KeyError(f"unknown algorithm {algo!r}; available: "
                           f"{sorted(ALGORITHMS)}")
        overrides = dict(self.fed_overrides)
        # lr/alpha/sigma0/delta/gamma are hyperparameter-point knobs the
        # executor feeds the program as traced inputs — an override here would
        # reach FederationConfig but never the simulation, a silent no-op.
        # Force them through the spec fields / axes instead.
        data_knobs = {"alpha", "sigma0", "delta", "gamma"} & set(overrides)
        if data_knobs:
            raise ValueError(
                f"set {sorted(data_knobs)} via SweepSpec fields or axes, not "
                f"fed_overrides (they are traced hyperparameter inputs)")
        kw: Dict[str, Any] = dict(
            algorithm=algo, num_clients=self.num_clients,
            local_steps=self.local_steps, gamma=self.gamma, delta=self.delta,
            sigma0=self.sigma0, alpha=self.alpha, **SCHEMES[scheme])
        kw.update(overrides)
        return FederationConfig(**kw)


@dataclass
class CellResult:
    """One grid cell's S-seed outcome at one hyperparameter point
    (host-side numpy)."""

    algo: str
    scheme: str
    seeds: Tuple[int, ...]
    rounds: int
    eval_rounds: List[int]          # [E] round index of each eval
    test_acc: np.ndarray            # [S, E]
    train_acc: np.ndarray           # [S] final train accuracy
    loss: np.ndarray                # [S, K] per-round mean train loss
    num_active: np.ndarray          # [S, K] active-client counts
    # the point's coordinates on the swept axes (lr/gamma/alpha/sigma0/delta)
    hparams: Dict[str, float] = field(default_factory=dict)
    # the row's strategy-axis coordinate ("sync" = the synchronous engine)
    strategy: str = "sync"
    # population the participation summary normalizes by (0: unknown/legacy)
    num_clients: int = 0
    # buffered-mode per-round traces (None for synchronous cells)
    commit: Optional[np.ndarray] = None             # [S, K] commit indicator
    commit_staleness: Optional[np.ndarray] = None   # [S, K] mean buffer age

    def final_test(self, window: int = 3) -> np.ndarray:
        """Per-seed mean test accuracy over the last ``window`` evals (the
        historical table-1 reduction)."""
        w = min(window, self.test_acc.shape[1])
        return self.test_acc[:, -w:].mean(axis=1)

    def summary(self) -> Dict[str, Dict[str, float]]:
        out = {"test_acc": summarize(self.final_test()),
               "train_acc": summarize(self.train_acc)}
        if self.num_clients and self.num_active.size:
            # mean per-round participation rate (of the materialized
            # population: m dense, C in cohort mode)
            out["participation"] = summarize(
                self.num_active.mean(axis=1) / self.num_clients)
        if self.commit is not None and self.commit.size:
            out.update(buffered_summary(self.commit, self.commit_staleness))
        return out


# --------------------------------------------------------------------------
# Executor with cross-cell compile/task/partition caches
# --------------------------------------------------------------------------

_TASK_CACHE: Dict[tuple, ClassificationTask] = {}
_TRACED_TASK_CACHE: Dict[tuple, TracedClassificationTask] = {}
_PARTITION_CACHE: Dict[tuple, np.ndarray] = {}
_RUNNER_CACHE: Dict[tuple, Any] = {}


def _task_key(spec: SweepSpec) -> tuple:
    """Structural dataset/model identity — deliberately alpha-free (the
    partition is a per-point traced input, not part of the task)."""
    return (spec.data_seed, spec.num_clients, spec.dim, spec.classes,
            spec.hidden, spec.n_per_class, spec.n_train,
            spec.per_client, spec.local_steps, spec.batch_size,
            spec.task, spec.lm_arch, spec.lm_d_model, spec.lm_layers,
            spec.lm_seq, spec.lm_n_seqs, spec.lm_n_test, spec.lm_reduced)


def get_task(spec: SweepSpec) -> ClassificationTask:
    """The constant-capturing task at the spec's scalar alpha (kept for the
    sequential baselines; the executor itself runs on ``get_traced_task``)."""
    if spec.task != "classification":
        raise ValueError(
            f"get_task covers the constant classification baseline only; "
            f"the {spec.task!r} workload is traced-only (get_traced_task)")
    key = _task_key(spec) + (spec.alpha,)
    if key not in _TASK_CACHE:
        _TASK_CACHE[key] = make_classification_task(
            data_seed=spec.data_seed, num_clients=spec.num_clients,
            dim=spec.dim, classes=spec.classes, hidden=spec.hidden,
            n_per_class=spec.n_per_class, n_train=spec.n_train,
            alpha=spec.alpha, per_client=spec.per_client,
            local_steps=spec.local_steps, batch_size=spec.batch_size)
    return _TASK_CACHE[key]


def get_traced_task(spec: SweepSpec) -> TracedClassificationTask:
    key = _task_key(spec)
    if key not in _TRACED_TASK_CACHE:
        if spec.task == "lm":
            _TRACED_TASK_CACHE[key] = make_traced_lm_task(
                data_seed=spec.data_seed, num_clients=spec.num_clients,
                arch=spec.lm_arch, d_model=spec.lm_d_model,
                layers=spec.lm_layers, seq_len=spec.lm_seq,
                classes=spec.classes, n_seqs=spec.lm_n_seqs,
                n_test=spec.lm_n_test, per_client=spec.per_client,
                local_steps=spec.local_steps, batch_size=spec.batch_size,
                reduced_widths=spec.lm_reduced)
        else:
            _TRACED_TASK_CACHE[key] = make_traced_classification_task(
                data_seed=spec.data_seed, num_clients=spec.num_clients,
                dim=spec.dim, classes=spec.classes, hidden=spec.hidden,
                n_per_class=spec.n_per_class, n_train=spec.n_train,
                per_client=spec.per_client, local_steps=spec.local_steps,
                batch_size=spec.batch_size)
    return _TRACED_TASK_CACHE[key]


def get_partition(spec: SweepSpec, alpha: float) -> np.ndarray:
    """Cached Dirichlet(alpha) index table for the spec's dataset."""
    key = _task_key(spec) + (alpha,)
    if key not in _PARTITION_CACHE:
        _PARTITION_CACHE[key] = get_traced_task(spec).partition(alpha)
    return _PARTITION_CACHE[key]


def _has_strategy_axis(spec: SweepSpec) -> bool:
    """Whether the spec runs the buffered engine: any strategy besides the
    bare synchronous default. (SYNC,) keeps the historical program — note a
    single non-sync strategy, or even (SYNC, buffered), flips the WHOLE
    cell onto the buffered trace; the degenerate SYNC knobs there reproduce
    the synchronous results bit-for-bit (tests/test_staleness.py)."""
    return spec.strategies != (SYNC,)


def _runner_for(spec: SweepSpec, fed: FederationConfig, task,
                metric_keys, shard_mesh=None) -> Any:
    # Everything swept reaches the compiled program through traced inputs —
    # zero the hyperparameter knobs so cells differing only in them share one
    # compiled runner, and canonicalize the algorithm name to its
    # state-compatible family so the cache is keyed by state STRUCTURE, not
    # by which member happens to run: every runner is built over the FULL
    # family table (the traced algo_id selects the member), so fedpbc and
    # fedavg cells hand back the same object. The runner's closures keep a
    # reference to `fed`, but consume only its structural fields (scheme,
    # local_steps, num_clients, per-family static knobs like fedau_K):
    # gamma/period go through traced hparams, and alpha/sigma0/delta never
    # leave the host (they shape p_base / the partition, both batch inputs).
    family = algo_family(fed.algorithm)
    canon = dataclasses.replace(fed, alpha=0.0, sigma0=0.0, delta=0.0,
                                gamma=0.0, period=0, algorithm=family[0])
    # use_kernel picks between two distinct traced programs (fused kernel vs
    # XLA switch), so the resolved bool is part of the cache key; within one
    # sweep the value is constant, so a whole grid still compiles each
    # (family, scheme) stage pair exactly once.
    use_kernel = resolve_use_kernel(spec.use_kernel)
    # the scale modes are distinct traced programs: cohort size changes
    # every client-axis shape, buffered threads a BufferState + knob inputs
    buffered = _has_strategy_axis(spec)
    # a 2-D shard_mesh bakes placement constraints into the trace, so it is
    # a distinct program; jax Meshes hash by (devices, axes), so equal
    # meshes share the cache entry
    key = (_task_key(spec), canon, spec.rounds, spec.eval_every,
           tuple(metric_keys), use_kernel, spec.cohort_size, buffered,
           shard_mesh)
    if key not in _RUNNER_CACHE:
        algo = make_algorithm_spec(family, fed)
        _RUNNER_CACHE[key] = make_batched_run_rounds(
            task.loss_fn, algo, fed,
            optimizer_factory=lambda hp: sgd(paper_decay(hp["lr"])),
            link_factory=lambda p, hp: make_link_process(
                p, fed, gamma=hp["gamma"], period=hp["period"]),
            source_factory=task.source_factory,
            init_params=task.init_params,
            num_rounds=spec.rounds,
            eval_every=spec.eval_every,
            eval_fn=task.eval_test,
            metric_keys=metric_keys,
            use_kernel=use_kernel,
            cohort_size=spec.cohort_size,
            buffered=buffered,
            shard_mesh=shard_mesh)
    return _RUNNER_CACHE[key]


def segment_runner_for(spec: SweepSpec, algo: str, scheme: str, *,
                       segment_rounds: int,
                       metric_keys=("loss", "num_active")) -> Any:
    """The adaptive-search controller's entry point into the runner cache
    (``repro.experiments.search``): a resumable ``carry_out`` runner that
    scans exactly ``segment_rounds`` rounds per dispatch, with
    ``eval_every == segment_rounds`` so each segment fires exactly one
    in-scan eval at its last round (the controller's prune signal).

    Cache discipline matches ``_runner_for``: the key is *structure-only*
    (task shape, zeroed-canonical fed config, segment length, metric keys,
    kernel/scale modes), so every candidate the controller ever packs —
    unseen lr/gamma values, re-batched survivor subsets, refilled fresh
    points — rides ONE compiled (init, scan) pair per (family, scheme);
    only the segment length itself is a new program. Shares
    ``_RUNNER_CACHE`` with the one-shot runners under a ``"segment"`` tag,
    and all task/partition/batch caches downstream."""
    task = get_traced_task(spec)
    fed = spec.cell_config(algo, scheme)
    family = algo_family(fed.algorithm)
    canon = dataclasses.replace(fed, alpha=0.0, sigma0=0.0, delta=0.0,
                                gamma=0.0, period=0, algorithm=family[0])
    use_kernel = resolve_use_kernel(spec.use_kernel)
    buffered = _has_strategy_axis(spec)
    key = ("segment", _task_key(spec), canon, segment_rounds,
           tuple(metric_keys), use_kernel, spec.cohort_size, buffered)
    if key not in _RUNNER_CACHE:
        algo_spec = make_algorithm_spec(family, fed)
        _RUNNER_CACHE[key] = make_batched_run_rounds(
            task.loss_fn, algo_spec, fed,
            optimizer_factory=lambda hp: sgd(paper_decay(hp["lr"])),
            link_factory=lambda p, hp: make_link_process(
                p, fed, gamma=hp["gamma"], period=hp["period"]),
            source_factory=task.source_factory,
            init_params=task.init_params,
            num_rounds=segment_rounds,
            eval_every=segment_rounds,
            eval_fn=task.eval_test,
            metric_keys=metric_keys,
            use_kernel=use_kernel,
            cohort_size=spec.cohort_size,
            buffered=buffered,
            carry_out=True)
    return _RUNNER_CACHE[key]


def point_base_probs(spec: SweepSpec, point: Dict[str, float]) -> jnp.ndarray:
    """Per-seed Eq.-9 connection-probability draws for one hyperparameter
    point, stacked to [S, m]. The per-seed key protocol (PRNGKey(seed)) is the
    historical one, so the default point reproduces ``seed_base_probs``."""
    return jnp.stack([
        build_base_probs(jax.random.PRNGKey(s), spec.num_clients,
                         spec.classes, alpha=point["alpha"],
                         sigma0=point["sigma0"], delta=point["delta"])[0]
        for s in spec.seeds])


def seed_base_probs(spec: SweepSpec) -> jnp.ndarray:
    """[S, m] draws at the spec's scalar (default) hyperparameter point."""
    return point_base_probs(
        spec, dict(alpha=spec.alpha, sigma0=spec.sigma0, delta=spec.delta))


_BATCH_CACHE: Dict[tuple, tuple] = {}


def _batch_key(spec: SweepSpec) -> tuple:
    """Identity of a spec's fed-independent batch contents (dataset/model
    shape, seed set, hyperparameter points). ONE definition shared by the
    host-side ``_BATCH_CACHE`` and the device-side ``_SHARDED_BATCH_CACHE``
    so the two can never desync on a future spec field."""
    return (_task_key(spec), spec.seeds, spec.strategies, spec.cohort_size,
            tuple(tuple(sorted(pt.items())) for pt in spec.hparam_points()))


def _batch_parts(spec: SweepSpec) -> tuple:
    """The fed-independent pieces of a cell batch (keys, p_base, lr/gamma
    arrays, partition stack), memoized per (dataset, seeds, points): a full
    grid calls ``make_cell_batch`` once per (algorithm, scheme) cell, and
    only the ``period`` array can differ between those calls."""
    points = spec.hparam_points()
    key = _batch_key(spec)
    if key not in _BATCH_CACHE:
        S = len(spec.seeds)
        seed_bundle = stack_seed_keys(spec.seeds)
        keys = jax.tree.map(lambda k: jnp.concatenate([k] * len(points)),
                            seed_bundle)
        # the Eq.-9 draw depends only on (alpha, sigma0, delta): memoize so
        # an lr/gamma-only ablation doesn't redo the sampling per point
        probs_memo: Dict[tuple, jnp.ndarray] = {}

        def probs(pt):
            k = (pt["alpha"], pt["sigma0"], pt["delta"])
            if k not in probs_memo:
                probs_memo[k] = point_base_probs(spec, pt)
            return probs_memo[k]

        p_base = jnp.concatenate([probs(pt) for pt in points])
        lr = jnp.asarray([pt["lr"] for pt in points for _ in range(S)],
                         jnp.float32)
        gamma = jnp.asarray([pt["gamma"] for pt in points for _ in range(S)],
                            jnp.float32)
        idx = jnp.asarray(np.stack([get_partition(spec, pt["alpha"])
                                    for pt in points for _ in range(S)]))
        _BATCH_CACHE[key] = (keys, p_base, lr, gamma, idx)
    return _BATCH_CACHE[key]


# {(batch_key, mesh): {"shared": replicated dataset, "groups": {algos:
# (sharded_batch, b_real)}}} — one base entry (the most recent (spec, mesh))
# whose ONE committed dataset copy is reused by every algorithm-group
# sub-entry, so a mixed-family sweep alternating groups per scheme neither
# thrashes the committed arrays nor pins one replicated dataset per family
_SHARDED_BATCH_CACHE: Dict[tuple, Dict[str, Any]] = {}


def _sharded_cell_batch(spec: SweepSpec, fed: FederationConfig,
                        task: TracedClassificationTask, mesh,
                        algos: Tuple[str, ...]) -> tuple:
    """``make_cell_batch`` padded to the mesh's device count and committed to
    it, memoized like ``_batch_parts``: one device transfer of the heavy
    fields (key/p_base/partition arrays, the replicated dataset — on real
    multi-host backends, real H2D traffic) per (dataset, seeds, points,
    algos, mesh). ``fed`` is deliberately NOT in the cache key: only the tiny
    ``[B_padded]`` ``period`` hparam vector depends on it, so it is rebuilt
    and committed per call — cells (or whole sweeps) differing only in a
    ``period`` override reuse the cached heavy arrays instead of pinning a
    duplicate copy per value. Returns ``(sharded_batch, B_real)``; equal
    meshes hash equal, so a fresh auto-resolved mesh over the same devices
    still hits.

    Unlike the host-side caches, this one holds DEVICE memory (a replicated
    dataset copy per device), so it keeps only the most recent (spec, mesh)
    base entry — with one sub-entry per algorithm group, since a
    mixed-family sweep alternates groups within one sweep (evicting per
    group would re-commit the heavy arrays once per (scheme, family)). The
    replicated dataset is committed ONCE at the base and shared by every
    group sub-entry (``shard_batch``'s device_put is a no-op on an array
    already carrying the target sharding), so a many-family sweep pins one
    dataset copy per device, not one per family. A long-lived process
    hopping specs/meshes still never accumulates committed duplicates
    beyond one sweep's groups."""
    base = _batch_key(spec) + (mesh,)
    entry = _SHARDED_BATCH_CACHE.get(base)
    if entry is None:
        _SHARDED_BATCH_CACHE.clear()
        entry = _SHARDED_BATCH_CACHE.setdefault(
            base, {"shared": None, "groups": {}})
    if algos not in entry["groups"]:
        batch = make_cell_batch(spec, fed, task, algos=algos)
        if entry["shared"] is None:
            entry["shared"] = jax.tree.map(
                lambda x: jax.device_put(x, replicated_sharding(mesh)),
                batch.shared)
        batch = dataclasses.replace(batch, shared=entry["shared"])
        padded, b_real = pad_batch(batch, mesh.shape["batch"])
        entry["groups"][algos] = (shard_batch(padded, mesh), b_real)
    sharded, b_real = entry["groups"][algos]
    lr = sharded.hparams["lr"]
    period = jax.device_put(
        jnp.full(lr.shape, float(fed.period), jnp.float32), lr.sharding)
    return CellBatch(keys=sharded.keys, p_base=sharded.p_base,
                     hparams=dict(sharded.hparams, period=period),
                     data=sharded.data, shared=sharded.shared,
                     algo_id=sharded.algo_id), b_real


def make_cell_batch(spec: SweepSpec, fed: FederationConfig,
                    task: TracedClassificationTask,
                    algos: Optional[Tuple[str, ...]] = None) -> CellBatch:
    """Flatten (algorithm x strategy x hyperparameter point x seed) into one
    [B]-leading batch, algo-major, then strategy-major, then point-major:
    ``b = ((algo_index * n_strategies + strategy_index) * n_points
    + point_index) * len(seeds) + seed_index`` (without a strategy axis,
    n_strategies == 1 and the historical layout is unchanged).

    ``algos`` (default: just ``fed.algorithm``) must all belong to one
    state-compatible family; the batch's ``algo_id`` column carries each
    trajectory's index into that family's canonical ``AlgorithmSpec`` table,
    so the same compiled family runner serves any subset. With a strategy
    axis (``_has_strategy_axis``), the per-trajectory buffer knobs travel
    as four more traced hparam columns."""
    if algos is None:
        algos = (fed.algorithm,)
    family = algo_family(algos[0])
    bad = [a for a in algos if a not in family]
    if bad:
        raise ValueError(
            f"algorithms {bad} are not state-compatible with {algos[0]!r} "
            f"(family {family}); run them as separate cells")
    ids = [family.index(a) for a in algos]
    keys, p_base, lr, gamma, idx = _batch_parts(spec)
    knobs: Dict[str, jnp.ndarray] = {}
    if _has_strategy_axis(spec):
        n_str = len(spec.strategies)
        rep_s = lambda x: jnp.concatenate([x] * n_str)
        keys = jax.tree.map(rep_s, keys)
        p_base, lr, gamma, idx = (rep_s(p_base), rep_s(lr), rep_s(gamma),
                                  rep_s(idx))
        knobs = strategy_knob_columns(spec.strategies,
                                      lr.shape[0] // n_str)
    if len(algos) > 1:
        rep = lambda x: jnp.concatenate([x] * len(algos))
        keys = jax.tree.map(rep, keys)
        p_base, lr, gamma, idx = rep(p_base), rep(lr), rep(gamma), rep(idx)
        knobs = {k: rep(v) for k, v in knobs.items()}
    hparams = {
        "lr": lr,
        "gamma": gamma,
        "period": jnp.full((lr.shape[0],), float(fed.period), jnp.float32),
        **knobs,
    }
    block = lr.shape[0] // len(algos)
    algo_id = jnp.asarray(np.repeat(ids, block), jnp.int32)
    return CellBatch(keys=keys, p_base=p_base, hparams=hparams,
                     data={"idx": idx}, shared=task.shared, algo_id=algo_id)


def _cell_program(spec: SweepSpec, algos: Tuple[str, ...], scheme: str,
                  metric_keys, mesh, devices) -> tuple:
    """What one (state-compatible algorithm group, scheme) cell dispatches:
    ``(task, runner, batch, B_real)``, the batch padded to and committed on
    the mesh when there is one (B_real is then the size before padding)."""
    task = get_traced_task(spec)
    fed = spec.cell_config(algos[0], scheme)
    if _has_strategy_axis(spec):
        metric_keys = tuple(metric_keys) + tuple(
            k for k in _BUFFER_KEYS if k not in metric_keys)
    batch_mesh = resolve_batch_mesh(mesh, devices)
    # a mesh with a "model" axis selects the 2-D path: the runner itself is
    # built for the mesh (in-trace placement constraints + spmd axis names)
    mesh2d = batch_mesh if (batch_mesh is not None
                            and "model" in batch_mesh.axis_names) else None
    runner = _runner_for(spec, fed, task, metric_keys, shard_mesh=mesh2d)
    if batch_mesh is None:
        batch = make_cell_batch(spec, fed, task, algos=algos)
        return task, runner, batch, batch.batch_size
    # memoized pad + device_put (shard.run_sharded is the uncached one-shot
    # equivalent)
    batch, b_real = _sharded_cell_batch(spec, fed, task, batch_mesh, algos)
    return task, runner, batch, b_real


def _run_batch(spec: SweepSpec, algos: Tuple[str, ...], scheme: str, *,
               metric_keys=("loss", "num_active"),
               mesh=AUTO, devices=None) -> List[CellResult]:
    """Run one (state-compatible algorithm group, scheme) cell: ALL algos x
    hyperparameter points x seeds in one batched program; returns
    ``CellResult`` rows algo-major, point-major. Each phase is a host span
    (``repro.telemetry``): ``sweep.batch``, ``sweep.dispatch``,
    ``sweep.wait`` (the host blocked on the device), ``sweep.train_eval``,
    ``sweep.rows``."""
    with telemetry.span("sweep.batch"):
        task, runner, batch, b_real = _cell_program(
            spec, algos, scheme, metric_keys, mesh, devices)
    with telemetry.span("sweep.dispatch"):
        states, out = runner(batch)
        if batch.batch_size != b_real:
            # padding rows are sliced off right here, so nothing downstream
            # ever sees them
            states, out = jax.tree.map(lambda x: x[:b_real], (states, out))
    with telemetry.span("sweep.wait"):
        jax.block_until_ready((states, out))

    with telemetry.span("sweep.train_eval"):
        if "evals" in out:
            test_acc = np.asarray(out["evals"])
            rounds_at = eval_rounds(spec.rounds, spec.eval_every)
        else:
            test_acc = np.asarray(jax.vmap(task.eval_test,
                                           in_axes=(0, None))(
                states.server, task.shared))[:, None]
            rounds_at = [spec.rounds]
        train_acc = np.asarray(jax.vmap(task.eval_train, in_axes=(0, None))(
            states.server, task.shared))
    with telemetry.span("sweep.rows"):
        buffered = _has_strategy_axis(spec)
        points = spec.hparam_points()
        S = len(spec.seeds)
        mets = {k: np.asarray(v) for k, v in out["metrics"].items()}
        strategies = spec.strategies
        n_str = len(strategies)
        B = len(algos) * n_str * len(points) * S
        # the per-round population the participation summary normalizes by
        pop = spec.cohort_size if spec.cohort_size is not None \
            else spec.num_clients

        def rows(a, ai, si, pi):
            lo = ((ai * n_str + si) * len(points) + pi) * S
            return a[lo:lo + S]

        return [
            CellResult(
                algo=algo, scheme=scheme, seeds=tuple(spec.seeds),
                rounds=spec.rounds, eval_rounds=rounds_at,
                test_acc=rows(test_acc, ai, si, pi),
                train_acc=rows(train_acc, ai, si, pi),
                loss=rows(mets.get("loss", np.zeros((B, 0))), ai, si, pi),
                num_active=rows(mets.get("num_active", np.zeros((B, 0))),
                                ai, si, pi),
                hparams=dict(pt),
                strategy=strat.name,
                # plain dense synchronous cells keep the historical two-key
                # summary; participation only appears where it is
                # informative (cohort mode normalizes by C, buffered rows by
                # the buffer pool)
                num_clients=(pop if (strat.name != "sync"
                                     or spec.cohort_size is not None) else 0),
                commit=(rows(mets["commit"], ai, si, pi) if buffered
                        else None),
                commit_staleness=(rows(mets["commit_staleness"], ai, si, pi)
                                  if buffered else None))
            for ai, algo in enumerate(algos)
            for si, strat in enumerate(strategies)
            for pi, pt in enumerate(points)]


def run_cell_batch(spec: SweepSpec, algo: str, scheme: str, *,
                   metric_keys=("loss", "num_active"),
                   mesh=AUTO, devices=None) -> List[CellResult]:
    """Run one (algo, scheme) cell: ALL hyperparameter points x seeds in one
    batched program; returns one ``CellResult`` per point. (The program is
    the algorithm's shared FAMILY runner with a constant ``algo_id`` column —
    ``run_sweep`` additionally joins whole state-compatible groups into one
    dispatch.)

    ``mesh``/``devices`` pick the execution placement (see
    ``repro.experiments.shard.resolve_batch_mesh``): by default the batch
    axis is sharded over a ``("batch",)`` mesh of all visible devices when
    more than one is up (B padded to a device multiple, padding dropped on
    the host), and runs on one device otherwise; ``mesh=None`` forces the
    single-device path, an explicit ``devices`` list or ``Mesh`` pins the
    placement. Per-trajectory results are identical either way, and both
    paths share the same cached runner (the compiled executables differ, the
    traced program does not).
    """
    return _run_batch(spec, (algo,), scheme, metric_keys=metric_keys,
                      mesh=mesh, devices=devices)


def run_cell(spec: SweepSpec, algo: str, scheme: str, *,
             metric_keys=("loss", "num_active"),
             mesh=AUTO, devices=None) -> CellResult:
    """Single-point convenience wrapper around ``run_cell_batch``."""
    n_points = len(spec.hparam_points()) * len(spec.strategies)
    if n_points != 1:       # before compiling/running anything
        raise ValueError(
            f"spec has {n_points} hyperparameter points x strategy rows; "
            f"use run_cell_batch for swept axes")
    return run_cell_batch(spec, algo, scheme, metric_keys=metric_keys,
                          mesh=mesh, devices=devices)[0]


def _algo_groups(spec: SweepSpec) -> List[Tuple[str, ...]]:
    """The spec's algorithms grouped into state-compatible families, each
    group one batched program, in first-appearance order."""
    groups: Dict[Tuple[str, ...], List[str]] = {}
    for algo in dict.fromkeys(spec.algorithms):   # unique, in order
        groups.setdefault(algo_family(algo), []).append(algo)
    return [tuple(g) for g in groups.values()]


_HLO_CACHE: Dict[tuple, Tuple[str, ...]] = {}


def sweep_hlo(spec: SweepSpec) -> Tuple[str, ...]:
    """The optimized HLO text of every program ``run_sweep(spec)``
    dispatches: each (algorithm group, scheme) cell's init and scan stages,
    lowered for the batch ``run_sweep`` builds and compiled as it compiles
    them (with the persistent compile cache, a load). Memoized per spec and
    default matmul precision, which changes the program.
    ``repro.telemetry.stage_seconds`` reads device time by stage through
    these texts."""
    key = (spec, str(jax.config.jax_default_matmul_precision))
    if key not in _HLO_CACHE:
        texts = []
        for scheme in spec.schemes:
            for group in _algo_groups(spec):
                _, runner, batch, _ = _cell_program(
                    spec, group, scheme, ("loss", "num_active"), AUTO, None)
                texts += [lowered.compile().as_text()
                          for lowered in runner.lower(batch)]
        _HLO_CACHE[key] = tuple(texts)
    return _HLO_CACHE[key]


@telemetry.span("sweep.run")
def run_sweep(spec: SweepSpec, *, store: Optional[ResultsStore] = None,
              suite: str = "sweep",
              metric_keys=("loss", "num_active"),
              mesh=AUTO, devices=None) -> List[CellResult]:
    """Execute the full grid; optionally append every (cell, hyperparameter
    point) row to ``store`` with its coordinates recorded (the ``algo``
    field is each row's algorithm-axis coordinate).

    Within each scheme, algorithms are grouped into state-compatible
    families (``repro.core.algo_family``) and every group runs as ONE
    batched program over the joint (algo x point x seed) axis; a mixed-state
    grid simply falls back to one program per family. Results (and store
    rows) keep the historical ``scheme -> algorithm -> point`` order
    regardless of how the groups executed."""
    # validate every cell upfront — a typo in the last algorithm must not
    # surface as a KeyError after earlier cells ran for minutes
    for scheme in spec.schemes:
        for algo in spec.algorithms:
            spec.cell_config(algo, scheme)
    cells = []
    for scheme in spec.schemes:
        by_algo: Dict[str, List[CellResult]] = {}
        n_points = len(spec.hparam_points()) * len(spec.strategies)
        pending = list(spec.algorithms)     # emission order (per occurrence)

        def emit(algo):
            for cell in by_algo[algo]:
                cells.append(cell)
                if store is not None:
                    arrays = {"test_acc": cell.test_acc,
                              "train_acc": cell.train_acc,
                              "loss": cell.loss,
                              "num_active": cell.num_active}
                    if cell.commit is not None:
                        arrays["commit"] = cell.commit
                        arrays["commit_staleness"] = cell.commit_staleness
                    store.append(
                        {"suite": suite, "algo": algo, "scheme": scheme,
                         "strategy": cell.strategy,
                         "seeds": list(spec.seeds), "rounds": spec.rounds,
                         "eval_every": spec.eval_every,
                         "hparams": dict(cell.hparams),
                         "spec": dataclasses.asdict(spec),
                         "eval_rounds": cell.eval_rounds,
                         "summary": cell.summary()},
                        arrays=arrays)

        # groups run in first-appearance order; completed results are emitted
        # (and PERSISTED) as soon as spec order allows, so a crash in a later
        # family (e.g. mifa's [m, ...] memory OOMing) never discards rows an
        # earlier family already computed
        try:
            for group in _algo_groups(spec):
                results = _run_batch(spec, group, scheme,
                                     metric_keys=metric_keys,
                                     mesh=mesh, devices=devices)
                for ai, algo in enumerate(group):
                    by_algo[algo] = results[ai * n_points:(ai + 1) * n_points]
                while pending and pending[0] in by_algo:
                    emit(pending.pop(0))
        finally:
            # no-op on success (pending drained); on a crash, salvage every
            # result a completed group already computed — including ones the
            # spec-order gate was still holding back behind the crashed
            # family (e.g. ("fedpbc", "fedau", "fedavg") with fedau failing:
            # fedavg ran with fedpbc and must persist too)
            for algo in pending:
                if algo in by_algo:
                    emit(algo)
    return cells
