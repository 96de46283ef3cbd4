"""Adaptive hyperparameter search over the sweep engine: successive halving
(ASHA-style) with elastic re-batching and host/device overlap.

The exhaustive grid burns a full ``rounds`` budget on every hyperparameter
point, including the ones that are visibly losing after a handful of evals.
This driver runs a candidate population in *rung-sized segments* on the
resumable scan-segment runner (``make_batched_run_rounds(carry_out=True)``
via ``grid.segment_runner_for``): each wave scans ``rung_rounds`` rounds for
every live candidate, ranks points on the in-scan eval fired at the segment
end, and keeps the top ``1/eta`` of each budget level; the rest are pruned
with their truncated trajectories persisted. Survivors' ``(FedState,
ds_state)`` carries are **elastically re-packed** into full-width
``CellBatch``es — the compiled program never runs half-empty — and because
the runner-cache key is structure-only, every re-pack, every unseen
hyperparameter value, and every refilled fresh candidate rides ONE compiled
(init, scan) pair per (family, scheme): zero new jit entries across the
whole search (``tests/test_search.py`` pins the counter).

Host/device overlap contract: at a prune point the host blocks ONLY on the
tiny ``[B]`` last-eval column of each batch (the ranking signal). The next
wave is packed and dispatched immediately; only then are the finished wave's
full metric trajectories pulled to the host and the stopped candidates' rows
persisted to the ``ResultsStore`` — the heavy result slicing runs while the
device is already scanning the next rung (the PR-4 loose end). In
``carry_out`` mode the carry is donated on non-CPU backends, so chaining
segments updates the [B]-state in place.

Rung math: a candidate's budget after surviving r waves is ``r *
rung_rounds``; ``base.rounds`` is the budget cap (``rung_rounds`` must
divide it), so a sole survivor keeps riding ``rung_rounds``-sized segments
until it graduates with the same total budget the exhaustive grid would
have spent on every point. With ``refill=True``, batch slots freed by
pruning are filled with freshly sampled candidates (up to
``max_candidates``) instead of duplicate padding; candidates are only
ranked against others at the SAME budget level, so a fresh level-0 filler
never knocks out a level-3 survivor on an unfair comparison.

CLI::

    PYTHONPATH=src python -m repro.experiments.search \
        --algo fedpbc --scheme bernoulli_tv --seeds 0,1 --clients 32 \
        --rounds 60 --rung-rounds 10 --candidates 16 --batch-points 8 \
        --space lr=log:0.01:0.5 gamma=uniform:0.1:0.9 \
        --out benchmarks/out/search
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.algorithms import algo_family
from repro.experiments.grid import (
    HPARAM_FIELDS,
    SweepSpec,
    get_partition,
    get_traced_task,
    point_base_probs,
    segment_runner_for,
)
from repro.experiments.results import ResultsStore, summarize
from repro.experiments.sweep import CellBatch, stack_seed_keys
from repro.scale.buffer import SYNC

SAMPLER_KINDS = ("log", "uniform", "choice")


@dataclass(frozen=True)
class SearchSpec:
    """One adaptive search: the protocol (``base``), the rung schedule, and
    the candidate space.

    ``base`` pins everything a ``SweepSpec`` pins — algorithm, scheme,
    seeds, client count, dataset/model shape — except the hyperparameter
    axes, which the sampler replaces: ``base.rounds`` is the per-candidate
    budget cap, ``base.eval_every`` is ignored (the eval cadence is
    ``rung_rounds``, one in-scan eval per segment). Exactly one algorithm,
    one scheme, and the synchronous strategy are supported per search (the
    cohort path composes; run several searches for several cells).

    ``space`` entries are ``(field, (kind, *args))`` with ``field`` in
    ``HPARAM_FIELDS`` and ``kind`` one of ``log`` (log-uniform in
    ``(lo, hi)``), ``uniform``, or ``choice`` (uniform over the listed
    values); unsampled fields keep ``base``'s scalar. ``points`` instead
    passes an explicit candidate pool (e.g. a grid, for an
    early-stopping-vs-exhaustive comparison); missing fields again default
    to ``base``'s scalars.
    """

    base: SweepSpec
    rung_rounds: int
    eta: int = 2
    num_candidates: int = 8
    # points per compiled batch (the elastic re-pack width W; batch width is
    # W * len(seeds) trajectories). None: the whole population in one batch.
    batch_points: Optional[int] = None
    space: Tuple[Tuple[str, tuple], ...] = ()
    points: Optional[Tuple[Dict[str, float], ...]] = None
    # fill partial batches with freshly sampled level-0 candidates (free
    # exploration in slots that would otherwise be duplicate padding)
    refill: bool = False
    max_candidates: Optional[int] = None    # total sampling cap for refill
    # stop the whole search once any candidate's point-mean eval reaches
    # this (time-to-target mode); None runs every survivor to the budget cap
    target: Optional[float] = None
    search_seed: int = 0

    def __post_init__(self):
        base = self.base
        for axis, n in (("algorithms", len(base.algorithms)),
                        ("schemes", len(base.schemes))):
            if n != 1:
                raise ValueError(
                    f"SearchSpec.base.{axis} has {n} entries; a search "
                    f"drives one (algorithm, scheme) cell — run one search "
                    f"per cell")
        if base.strategies != (SYNC,):
            raise ValueError(
                "SearchSpec.base.strategies must be (SYNC,): the controller "
                "ranks on the synchronous eval contract")
        hp_axes = [f for f in HPARAM_FIELDS if getattr(base, f + "s")]
        if hp_axes:
            raise ValueError(
                f"SearchSpec.base carries swept axes {hp_axes}; the search "
                f"samples its own points — pass them via space= or points=")
        if self.rung_rounds < 1:
            raise ValueError(f"rung_rounds={self.rung_rounds} must be >= 1")
        if base.rounds % self.rung_rounds:
            raise ValueError(
                f"rung_rounds={self.rung_rounds} must divide the budget cap "
                f"base.rounds={base.rounds} (segments are same-length by "
                f"construction — one scan compile)")
        if self.eta < 2:
            raise ValueError(f"eta={self.eta} must be >= 2")
        if self.points is not None:
            if not self.points:
                raise ValueError("points= is empty; give at least one "
                                 "candidate")
            for pt in self.points:
                bad = sorted(set(pt) - set(HPARAM_FIELDS))
                if bad:
                    raise ValueError(
                        f"points entry has unknown fields {bad}; "
                        f"hyperparameter fields are {HPARAM_FIELDS}")
        elif self.num_candidates < 1:
            raise ValueError(
                f"num_candidates={self.num_candidates} must be >= 1")
        for name, dist in self.space:
            if name not in HPARAM_FIELDS:
                raise ValueError(
                    f"space field {name!r} is not a hyperparameter; "
                    f"expected one of {HPARAM_FIELDS}")
            kind = dist[0] if dist else None
            if kind not in SAMPLER_KINDS:
                raise ValueError(
                    f"space[{name!r}] kind {kind!r}; expected one of "
                    f"{SAMPLER_KINDS}")
            if kind in ("log", "uniform"):
                if len(dist) != 3 or not dist[1] < dist[2]:
                    raise ValueError(
                        f"space[{name!r}]=({kind}, lo, hi) needs lo < hi, "
                        f"got {dist[1:]}")
                if kind == "log" and dist[1] <= 0:
                    raise ValueError(
                        f"space[{name!r}] log-sampling needs lo > 0, got "
                        f"{dist[1]}")
            elif len(dist) < 2 or not dist[1]:
                raise ValueError(
                    f"space[{name!r}]=('choice', (v, ...)) needs at least "
                    f"one value")
        if self.batch_points is not None and self.batch_points < 1:
            raise ValueError(
                f"batch_points={self.batch_points} must be >= 1")
        if self.refill and not self.space:
            raise ValueError(
                "refill=True needs a space= to sample fresh candidates from")
        pop = len(self.points) if self.points is not None \
            else self.num_candidates
        if self.max_candidates is not None and self.max_candidates < pop:
            raise ValueError(
                f"max_candidates={self.max_candidates} is below the initial "
                f"population {pop}")

    @property
    def population(self) -> int:
        return len(self.points) if self.points is not None \
            else self.num_candidates

    @property
    def width(self) -> int:
        """Points per compiled batch — the fixed pack width W."""
        return min(self.batch_points or self.population, self.population)

    @property
    def max_level(self) -> int:
        """Segments to the budget cap (a candidate's level is its count of
        completed segments; budget = level * rung_rounds)."""
        return self.base.rounds // self.rung_rounds


def sample_point(rng: np.random.Generator,
                 search: SearchSpec) -> Dict[str, float]:
    """Draw one candidate from ``search.space`` (unsampled fields keep the
    base spec's scalar knobs)."""
    pt = {f: float(getattr(search.base, f)) for f in HPARAM_FIELDS}
    for name, dist in search.space:
        kind = dist[0]
        if kind == "log":
            lo, hi = float(dist[1]), float(dist[2])
            pt[name] = float(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        elif kind == "uniform":
            pt[name] = float(rng.uniform(float(dist[1]), float(dist[2])))
        else:   # choice
            vals = dist[1]
            pt[name] = float(vals[int(rng.integers(len(vals)))])
    return pt


@dataclass
class Candidate:
    """Host-side bookkeeping for one search candidate (a hyperparameter
    point across all seeds)."""

    cid: int
    point: Dict[str, float]
    level: int = 0                  # completed rung_rounds-sized segments
    rung: int = 0                   # prune points survived
    status: str = "alive"           # alive | pruned | finished | stopped
    evals: List[float] = field(default_factory=list)    # point-mean, per seg
    test_acc: List[np.ndarray] = field(default_factory=list)    # [S] per seg
    metrics: Dict[str, List[np.ndarray]] = field(default_factory=dict)
    pool_point: int = -1            # point index into the last wave's carry
    record_id: Optional[int] = None

    @property
    def last_eval(self) -> float:
        return self.evals[-1] if self.evals else float("-inf")


@dataclass
class SearchOutcome:
    """What one ``run_search`` spent and found."""

    candidates: List[Candidate]
    waves: int
    # trajectory-rounds dispatched: Sum over batches of W * S * rung_rounds
    # (seeds and duplicate-padding slots included — they burn device work)
    total_device_rounds: int
    # per wave: cumulative device rounds + the best point-mean eval so far
    wave_log: List[Dict[str, float]]
    target_hit: bool
    compile_entries: Dict[str, Optional[int]]

    @property
    def best(self) -> Candidate:
        return max((c for c in self.candidates if c.evals),
                   key=lambda c: (c.last_eval, c.level))

    def device_rounds_to(self, target: float) -> Optional[int]:
        """Cumulative device rounds at the first wave whose best eval
        reached ``target`` (None: never reached)."""
        for entry in self.wave_log:
            if entry["best_eval"] >= target - 1e-9:
                return int(entry["device_rounds"])
        return None


def run_search(search: SearchSpec, *, store: Optional[ResultsStore] = None,
               suite: str = "search",
               metric_keys=("loss", "num_active"),
               verbose: bool = False) -> SearchOutcome:
    """Run one successive-halving search; optionally persist one store row
    per candidate (truncated trajectories for pruned points, full-budget
    ones for finished points), each stamped with ``search`` provenance
    (rung, budget_rounds, status) that ``results.cell_key`` folds into the
    row's identity."""
    spec = search.base
    algo, scheme = spec.algorithms[0], spec.schemes[0]
    task = get_traced_task(spec)
    fed = spec.cell_config(algo, scheme)
    family = algo_family(algo)
    algo_idx = family.index(algo)
    runner = segment_runner_for(spec, algo, scheme,
                                segment_rounds=search.rung_rounds,
                                metric_keys=metric_keys)
    seg = search.rung_rounds
    S = len(spec.seeds)
    W = search.width
    max_level = search.max_level
    rng = np.random.default_rng(search.search_seed)
    seed_bundle = stack_seed_keys(spec.seeds)

    defaults = {f: float(getattr(spec, f)) for f in HPARAM_FIELDS}
    if search.points is not None:
        pool = [dict(defaults, **pt) for pt in search.points]
    else:
        pool = [sample_point(rng, search)
                for _ in range(search.num_candidates)]
    cap = search.max_candidates if search.max_candidates is not None \
        else len(pool)
    candidates = [Candidate(cid=i, point=pt) for i, pt in enumerate(pool)]

    # the Eq.-9 draw depends only on (alpha, sigma0, delta); memoize across
    # waves so re-packs never redo host-side sampling
    probs_memo: Dict[tuple, jnp.ndarray] = {}

    def probs(pt):
        k = (pt["alpha"], pt["sigma0"], pt["delta"])
        if k not in probs_memo:
            probs_memo[k] = point_base_probs(spec, pt)
        return probs_memo[k]

    def build_batch(pts: List[Dict[str, float]]) -> CellBatch:
        keys = jax.tree.map(lambda k: jnp.concatenate([k] * len(pts)),
                            seed_bundle)
        p_base = jnp.concatenate([probs(pt) for pt in pts])
        lr = jnp.asarray([pt["lr"] for pt in pts for _ in range(S)],
                         jnp.float32)
        gamma = jnp.asarray([pt["gamma"] for pt in pts for _ in range(S)],
                            jnp.float32)
        idx = jnp.asarray(np.stack([get_partition(spec, pt["alpha"])
                                    for pt in pts for _ in range(S)]))
        hparams = {"lr": lr, "gamma": gamma,
                   "period": jnp.full((lr.shape[0],), float(fed.period),
                                      jnp.float32)}
        return CellBatch(keys=keys, p_base=p_base, hparams=hparams,
                         data={"idx": idx}, shared=task.shared,
                         algo_id=jnp.full((lr.shape[0],), algo_idx,
                                          jnp.int32))

    prev_pool = None                # concatenated last-wave carry [P*W*S]
    total_rounds = 0
    wave_log: List[Dict[str, float]] = []
    target_hit = False
    waves = 0

    def dispatch_wave(alive: List[Candidate]):
        """Pack the live population into full-width batches (survivors
        carried, level-0 slots freshly inited, leftover slots refilled or
        duplicate-padded) and dispatch every segment. Returns the list of
        ``(occupants, n_real, carry, out)`` async handles."""
        nonlocal total_rounds
        # deterministic pack order: deepest budget first (survivors stay
        # contiguous across re-packs), best-eval-first within a level
        alive = sorted(alive, key=lambda c: (-c.level, -c.last_eval, c.cid))
        groups = [alive[i:i + W] for i in range(0, len(alive), W)]
        last = groups[-1]
        while len(last) < W and search.refill and search.space \
                and len(candidates) < cap:
            c = Candidate(cid=len(candidates),
                          point=sample_point(rng, search))
            candidates.append(c)
            last.append(c)
        handles = []
        for occ in groups:
            n_real = len(occ)
            # duplicate-pad to full width; padded slots replicate occupant
            # 0 (its carry AND its batch columns) and are dropped on read
            occ = occ + [occ[0]] * (W - n_real) if n_real < W else occ
            batch = build_batch([c.point for c in occ])
            cont = np.array([c.level > 0 for c in occ])
            rows = np.zeros((W * S,), np.int64)
            for j, c in enumerate(occ):
                if c.level > 0:
                    rows[j * S:(j + 1) * S] = c.pool_point * S + np.arange(S)
            if cont.all():
                carry = jax.tree.map(lambda x: x[jnp.asarray(rows)],
                                     prev_pool)
            elif not cont.any():
                carry = runner.init(batch)
            else:
                # mixed batch: survivors gather from the previous wave's
                # pool, fresh (refilled) slots take the batched init
                fresh = runner.init(batch)
                mask = jnp.asarray(np.repeat(cont, S))

                def pick(p, f):
                    sel = mask.reshape((mask.shape[0],)
                                       + (1,) * (f.ndim - 1))
                    return jnp.where(sel, p[jnp.asarray(rows)], f)

                carry = jax.tree.map(pick, prev_pool, fresh)
            # async dispatch; on donating backends the passed carry is
            # consumed here — `carry` is rebound to the segment's output
            carry, out = runner.step(carry, batch)
            total_rounds += W * S * seg
            handles.append((occ, n_real, carry, out))
        return handles

    def drain(handles) -> None:
        """Pull a finished wave's full metric trajectories to the host and
        persist every candidate the prune step stopped — the heavy
        transfers and store writes, running AFTER the next wave was
        dispatched (host work overlapped with device compute)."""
        for occ, n_real, _, out in handles:
            host = {k: np.asarray(v) for k, v in out["metrics"].items()}
            acc = np.asarray(out["evals"])
            for j, c in enumerate(occ[:n_real]):
                rows = slice(j * S, (j + 1) * S)
                c.test_acc.append(acc[rows, -1])
                for k in metric_keys:
                    c.metrics.setdefault(k, []).append(host[k][rows])
        if store is None:
            return
        for occ, n_real, _, _ in handles:
            for c in occ[:n_real]:
                if c.status != "alive" and c.record_id is None:
                    persist(c)

    def persist(c: Candidate) -> None:
        budget = c.level * seg
        ta = np.stack(c.test_acc, axis=1)           # [S, E]
        w = min(3, ta.shape[1])
        rec = {
            "suite": suite, "algo": algo, "scheme": scheme,
            "strategy": "sync", "seeds": list(spec.seeds),
            "rounds": budget, "eval_every": seg,
            "hparams": dict(c.point),
            "spec": dataclasses.asdict(dataclasses.replace(
                spec, rounds=budget, eval_every=seg)),
            "eval_rounds": [seg * (i + 1) for i in range(c.level)],
            "search": {"rung": c.rung, "budget_rounds": budget,
                       "status": c.status, "cid": c.cid,
                       "rung_rounds": seg, "eta": search.eta,
                       "population": search.population},
            "summary": {"test_acc": summarize(ta[:, -w:].mean(axis=1))},
        }
        arrays = {"test_acc": ta}
        for k in metric_keys:
            arrays[k] = np.concatenate(c.metrics[k], axis=1)
        c.record_id = store.append(rec, arrays=arrays)["record_id"]

    def prune(handles) -> None:
        """The prune point: block only on the [W] last-eval column of each
        batch, then decide who survives. Candidates are ranked within their
        own budget level; each level keeps ceil(n / eta)."""
        nonlocal target_hit
        advanced: List[Candidate] = []
        best_eval = float("-inf")
        for occ, n_real, _, out in handles:
            col = np.asarray(out["evals"][:, -1]).reshape(W, S).mean(axis=1)
            for j, c in enumerate(occ[:n_real]):
                c.level += 1
                c.evals.append(float(col[j]))
                advanced.append(c)
                best_eval = max(best_eval, c.evals[-1])
        wave_log.append({"device_rounds": total_rounds,
                         "best_eval": best_eval})
        for c in advanced:
            if c.level >= max_level:
                c.status = "finished"
        if search.target is not None and best_eval >= search.target - 1e-9:
            target_hit = True
            for c in advanced:
                if c.status == "alive":
                    c.status = "stopped"
            return
        by_level: Dict[int, List[Candidate]] = {}
        for c in advanced:
            if c.status == "alive":
                by_level.setdefault(c.level, []).append(c)
        for grp in by_level.values():
            grp.sort(key=lambda c: (-c.last_eval, c.cid))
            keep = -(-len(grp) // search.eta)       # ceil: never kill a level
            for c in grp[:keep]:
                c.rung += 1
            for c in grp[keep:]:
                c.status = "pruned"

    pending = None
    while True:
        alive = [c for c in candidates if c.status == "alive"]
        if not alive:
            break
        handles = dispatch_wave(alive)
        waves += 1
        if pending is not None:
            drain(pending)      # overlapped: device is scanning this wave
        prune(handles)
        if verbose:
            n_alive = sum(c.status == "alive" for c in candidates)
            print(f"# search wave {waves}: {len(handles)} batch(es), "
                  f"best_eval={wave_log[-1]['best_eval']:.4f}, "
                  f"alive={n_alive}, device_rounds={total_rounds}",
                  flush=True)
        # carries of this wave become the next re-pack's gather pool
        parts = [carry for _, _, carry, _ in handles]
        prev_pool = parts[0] if len(parts) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs), *parts)
        for bi, (occ, n_real, _, _) in enumerate(handles):
            for j, c in enumerate(occ[:n_real]):
                c.pool_point = bi * W + j
        pending = handles
    if pending is not None:
        drain(pending)

    from repro.analysis.sanitize import cache_size
    entries = {"init": cache_size(runner.init_batch),
               "scan": cache_size(runner.scan_batch)}
    return SearchOutcome(candidates=candidates, waves=waves,
                         total_device_rounds=total_rounds,
                         wave_log=wave_log, target_hit=target_hit,
                         compile_entries=entries)


def _parse_space(items) -> Tuple[Tuple[str, tuple], ...]:
    """``name=kind:v1:v2[:v3...]`` -> SearchSpec.space entries (choice takes
    every listed value)."""
    out = []
    for item in items:
        try:
            name, rest = item.split("=", 1)
            kind, *vals = rest.split(":")
            vals = tuple(float(v) for v in vals)
        except ValueError:
            raise SystemExit(
                f"--space entry {item!r}; expected name=kind:v1:v2[:...] "
                f"(e.g. lr=log:0.01:0.5 or alpha=choice:0.1:1.0)")
        out.append((name, (kind, vals) if kind == "choice"
                    else (kind,) + vals))
    return tuple(out)


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="Successive-halving (ASHA-style) hyperparameter search "
                    "over the batched sweep engine: candidates run in "
                    "rung-sized scan segments, losers are pruned on in-scan "
                    "evals, survivors are elastically re-packed into full "
                    "batches of ONE compiled program.")
    ap.add_argument("--algo", default="fedpbc")
    ap.add_argument("--scheme", default="bernoulli_ti")
    ap.add_argument("--seeds", default="0,1", help="comma list of ints")
    ap.add_argument("--rounds", type=int, default=40,
                    help="per-candidate budget cap (a multiple of "
                    "--rung-rounds)")
    ap.add_argument("--rung-rounds", type=int, default=10,
                    help="segment length: rounds between prune points")
    ap.add_argument("--eta", type=int, default=2,
                    help="keep top 1/eta of each budget level per prune")
    ap.add_argument("--candidates", type=int, default=8)
    ap.add_argument("--batch-points", type=int, default=None,
                    help="points per compiled batch (default: the whole "
                    "population)")
    ap.add_argument("--space", nargs="*", default=["lr=log:0.01:0.5"],
                    help="sampler per hyperparameter: name=kind:v1:v2[:...] "
                    "with kind in log|uniform|choice")
    ap.add_argument("--refill", action="store_true",
                    help="fill freed batch slots with fresh candidates")
    ap.add_argument("--max-candidates", type=int, default=None,
                    help="total sampling cap when refilling")
    ap.add_argument("--target", type=float, default=None,
                    help="stop the search once any candidate reaches this "
                    "test accuracy")
    ap.add_argument("--search-seed", type=int, default=0)
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--out", default="benchmarks/out/search",
                    help="results-store directory (JSONL + npz)")
    ap.add_argument("--suite", default="search",
                    help="suite tag on the records")
    args = ap.parse_args(argv)

    base = SweepSpec(
        algorithms=(args.algo,), schemes=(args.scheme,),
        seeds=tuple(int(s) for s in args.seeds.split(",")),
        rounds=args.rounds, eval_every=args.rung_rounds,
        num_clients=args.clients, local_steps=args.local_steps)
    search = SearchSpec(
        base=base, rung_rounds=args.rung_rounds, eta=args.eta,
        num_candidates=args.candidates, batch_points=args.batch_points,
        space=_parse_space(args.space), refill=args.refill,
        max_candidates=args.max_candidates, target=args.target,
        search_seed=args.search_seed)
    store = ResultsStore(args.out)
    outcome = run_search(search, store=store, suite=args.suite, verbose=True)
    print("search,cid,status,rung,budget_rounds,hparams,last_eval",
          flush=True)
    for c in sorted(outcome.candidates, key=lambda c: -c.last_eval):
        hp = ";".join(f"{k}={v:g}" for k, v in sorted(c.point.items()))
        ev = f"{c.last_eval:.4f}" if c.evals else "nan"
        print(f"search,{c.cid},{c.status},{c.rung},"
              f"{c.level * args.rung_rounds},{hp},{ev}", flush=True)
    best = outcome.best
    grid_rounds = (len(outcome.candidates) * len(base.seeds) * args.rounds)
    print(f"# best cid={best.cid} eval={best.last_eval:.4f} | "
          f"device_rounds={outcome.total_device_rounds} "
          f"(exhaustive grid of the same pool: {grid_rounds}) | "
          f"waves={outcome.waves} target_hit={outcome.target_hit}",
          flush=True)
    print(f"# results appended to {store.path}", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
