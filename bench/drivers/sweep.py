"""Sweep cells: the program's ``run_sweep`` over a grid read from data.

The configuration file gives the client model and data set (``sizes``), the
traffic file the grid: algorithms, link scheme, clients, local steps,
batch, learning rates, how many seeds, rounds and eval cadence. One window
step is one ``run_sweep`` call over the whole grid; its work is the number
of trajectory-rounds it simulates.

Correctness: every trajectory of the last window call is followed through
all of its rounds by the plain reference (``bench.refs.paper_mlp``): the
mean client loss of its first ``loss_rounds`` rounds to a relative gap
(``loss_gap``), and the active-client count of every round exactly
(``active_mismatch``). The norm of the server parameters' change is
compared by the worst leaf (:func:`change_gap`) twice: over the first
``loss_rounds`` rounds (``change_gap``), read from one more ``run_sweep``
call of that length made after the window, whose rows must be the window
call's first rounds exactly; and over the whole call (``call_change_gap``),
where round-off between two float32 programs has grown over 50 rounds of
SGD, as a guard against gross faults. ``run_sweep`` returns metrics only,
so the server parameters are read from the program's batched runners as
the calls leave them (:class:`Capture`).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional
from unittest import mock

import numpy as np

from bench import flops
from bench.refs import paper_mlp as ref

UNIT = "traj_rounds_per_s"
SPAN = "bench.run_sweep"
# a leaf whose reference change is below this share of the median leaf's
# moves by round-off alone and is not compared
STILL = 1e-3


class Capture:
    """Stands in for one of the program's cached batched runners and keeps
    what its latest call returned, ``(states, out)``."""

    def __init__(self, inner):
        self.inner, self.last = inner, None

    def __call__(self, *args, **kwargs):
        self.last = self.inner(*args, **kwargs)
        return self.last

    def __getattr__(self, name):
        return getattr(self.inner, name)


def capture_runners() -> None:
    """Wrap every runner in the program's runner cache (once) and clear what
    they hold."""
    from repro.experiments import grid

    cache = grid._RUNNER_CACHE
    for key, runner in cache.items():
        if not isinstance(runner, Capture):
            cache[key] = Capture(runner)
    for runner in cache.values():
        runner.last = None


class Workload:
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        import jax

        from repro.experiments import SweepSpec

        self.jax = jax
        self.config, self.traffic = config, traffic
        sizes = config["sizes"]
        self.precision = config["matmul_precision"]
        self.spec = SweepSpec(
            algorithms=tuple(traffic["algorithms"]),
            schemes=(traffic["scheme"],),
            seeds=tuple(seed + i for i in range(traffic["seeds_per_run"])),
            lrs=tuple(traffic["lrs"]),
            rounds=traffic["rounds"], eval_every=traffic["eval_every"],
            num_clients=traffic["clients"],
            local_steps=traffic["local_steps"],
            batch_size=traffic["batch"],
            data_seed=seed, dim=sizes["dim"], hidden=sizes["hidden"],
            classes=sizes["classes"], n_per_class=sizes["n_per_class"],
            n_train=sizes["n_train"], per_client=sizes["per_client"],
            alpha=sizes["alpha"], sigma0=sizes["sigma0"],
            delta=sizes["delta"], gamma=sizes["gamma"])
        # the same grid over the rounds whose loss is compared
        n = traffic["loss_rounds"]
        self.early_spec = dataclasses.replace(self.spec, rounds=n,
                                              eval_every=n)
        self.trajectories = (len(self.spec.algorithms)
                             * len(self.spec.hparam_points())
                             * len(self.spec.seeds))
        self.work_per_step = self.trajectories * self.spec.rounds
        self.first: Optional[List] = None
        self.last: Optional[List] = None
        self.failed = 0

    def _ctx(self):
        return self.jax.default_matmul_precision(self.precision)

    def _call(self, spec=None):
        from repro.experiments import run_sweep

        with self._ctx(), self.jax.profiler.TraceAnnotation(SPAN):
            return run_sweep(spec or self.spec)

    def prepare(self) -> None:
        """Compiles (or loads) and runs the grid once; that call's rows are
        what every later call must repeat. Later calls leave their server
        parameters with :class:`Capture`."""
        self.first = self.last = self._call()
        capture_runners()

    def step(self) -> int:
        cells = self._call()
        if not _same(cells, self.first) or not _finite(cells):
            self.failed += 1
        self.last = cells
        return self.work_per_step

    def finish(self) -> None:
        """``run_sweep`` returns host arrays: nothing is in flight."""

    def release(self) -> None:
        """The grid's device state is a few MB; the reference fits beside
        it, so nothing is freed."""

    def server_of_last_call(self, cells) -> List[Dict[str, np.ndarray]]:
        """The server parameters the last call ended with, one
        trajectory per row in the order of :meth:`trajectories_of`, after
        checking that it is the one runner call made since
        :func:`capture_runners` and that its metrics are that call's
        rows."""
        from repro.experiments import grid

        live = [r.last for r in grid._RUNNER_CACHE.values()
                if isinstance(r, Capture) and r.last is not None]
        if len(live) != 1:
            raise RuntimeError(f"expected one runner call, found {len(live)}")
        states, out = live[0]
        loss = np.asarray(out["metrics"]["loss"])
        rows = np.concatenate([c.loss for c in cells])
        if not np.array_equal(loss, rows, equal_nan=True):
            raise RuntimeError("the runner's last call is not the rows'")
        server = {k: np.asarray(v) for k, v in states.server.items()}
        return [{k: v[b] for k, v in server.items()}
                for b in range(len(rows))]

    def call_servers(self, spec):
        """One more call of ``spec``, outside the window: its rows and the
        server parameters it ended with (a runner it builds is captured
        too)."""
        from repro.experiments import grid

        capture_runners()
        build = grid.make_batched_run_rounds
        with mock.patch.object(grid, "make_batched_run_rounds",
                               lambda *a, **kw: Capture(build(*a, **kw))):
            cells = self._call(spec)
        return cells, self.server_of_last_call(cells)

    # -- correctness -------------------------------------------------------

    def proto(self) -> ref.Protocol:
        s, sz = self.spec, self.config["sizes"]
        tv = {"bernoulli_tv": True, "bernoulli_ti": False}[s.schemes[0]]
        return ref.Protocol(m=s.num_clients, local_steps=s.local_steps,
                            batch=s.batch_size, per_client=sz["per_client"],
                            dim=sz["dim"], hidden=sz["hidden"],
                            classes=sz["classes"], time_varying=tv,
                            period=float(sz["period"]))

    def trajectories_of(self, cells):
        """``(row, seed index, reference Trajectory)`` for every
        trajectory of ``cells``."""
        gamma = self.config["sizes"]["gamma"]
        for cell in cells:
            for j, seed in enumerate(cell.seeds):
                yield cell, j, ref.Trajectory(
                    algo=cell.algo, lr=cell.hparams["lr"], seed=seed,
                    gamma=gamma)

    def reference(self, cells, mode: str = "highest",
                  keep=None) -> List[Dict]:
        """The reference's run of every trajectory of ``cells``, through all
        of the call's rounds, in their order, at ``mode``, with its server
        parameters after each round count in ``keep`` (by default the
        compared ones: ``loss_rounds`` and the call's)."""
        s, sz = self.spec, self.config["sizes"]
        data = ref.dataset(s.data_seed, dim=sz["dim"], classes=sz["classes"],
                           n_per_class=sz["n_per_class"],
                           n_train=sz["n_train"], sep=sz["sep"])
        idx = ref.dirichlet_split(s.data_seed, data["y"], s.num_clients,
                                  sz["alpha"], sz["per_client"])
        probs = {seed: ref.uplink_probs(
            seed, s.num_clients, sz["classes"], alpha=sz["alpha"],
            sigma0=sz["sigma0"], delta=sz["delta"]) for seed in s.seeds}
        keep = keep or (self.early_spec.rounds, s.rounds)
        return [ref.follow(traj, self.proto(), data, idx, probs[traj.seed],
                           s.rounds, mode, keep)
                for _, _, traj in self.trajectories_of(cells)]

    def rows(self, cells) -> List[Dict]:
        """The program's rounds of every trajectory of the last window
        call, in the reference's layout, with the server parameters of that
        call and of one more over its first ``loss_rounds`` rounds, whose
        rows must be the window call's first rounds exactly."""
        call = self.server_of_last_call(cells)
        n = self.early_spec.rounds
        early_cells, early = self.call_servers(self.early_spec)
        if not same_rounds(early_cells, cells, n):
            raise RuntimeError(f"the call over {n} rounds does not repeat "
                               f"the window call's first {n}")
        return [{"loss": cell.loss[j], "num_active": cell.num_active[j],
                 "servers": {n: e, self.spec.rounds: c}}
                for (cell, j, _), e, c in zip(self.trajectories_of(cells),
                                              early, call)]

    def compare(self, rows: List[Dict], refs: List[Dict]) -> Dict[str, float]:
        """Worst relative loss gap over the first ``loss_rounds`` rounds;
        worst relative gap of a leaf's change norm (:func:`change_gap`)
        over those rounds and over the whole call; the count of rounds
        whose active clients differ."""
        n, k = self.early_spec.rounds, self.spec.rounds
        gap, early, call, active = 0.0, 0.0, 0.0, 0
        for p, r in zip(rows, refs):
            loss = np.asarray(p["loss"][:n], np.float64)
            gap = _worst(gap, float(np.max(np.abs(loss - r["loss"][:n])
                                           / np.abs(r["loss"][:n]))))
            early = _worst(early, change_gap(p["servers"][n],
                                             r["servers"][n], r["init"]))
            call = _worst(call, change_gap(p["servers"][k], r["servers"][k],
                                           r["init"]))
            active += int(np.sum(np.asarray(p["num_active"])
                                 != r["num_active"]))
        return {"loss_gap": gap, "change_gap": early,
                "call_change_gap": call, "active_mismatch": float(active)}

    def check(self) -> Dict[str, float]:
        return self.compare(self.rows(self.last), self.reference(self.last))

    def control(self, mode: str) -> Dict[str, float]:
        """The control's readings: the reference at ``mode`` in the
        program's place (the grid's layout comes from one program call)."""
        if self.first is None:
            self.prepare()
        return self.compare(self.reference(self.first, mode),
                            self.reference(self.first))

    # -- per-layer inputs --------------------------------------------------

    def train_flops_per_unit(self) -> float:
        sz, s = self.config["sizes"], self.spec
        return flops.mlp_train_flops_per_traj_round(
            dim=sz["dim"], hidden=sz["hidden"], classes=sz["classes"],
            clients=s.num_clients,
            local_steps=s.local_steps, batch=s.batch_size)


def leaf_gaps(program: Dict, reference: Dict, init: Dict) -> Dict[str, float]:
    """Each leaf's gap between the program's and the reference's norm of
    the change from ``init``, over the reference's norm of that leaf or of
    the median leaf, whichever is larger. Leaves the reference moves by
    less than :data:`STILL` of the median leaf's are left out; where the
    reference's server did not move, the program's must not either."""
    ref_norm = {k: float(np.linalg.norm(np.asarray(reference[k], np.float64)
                                        - init[k])) for k in reference}
    median = float(np.median(list(ref_norm.values())))
    gaps = {}
    for k, r in ref_norm.items():
        if r < STILL * median:
            continue
        p = float(np.linalg.norm(np.asarray(program[k], np.float64)
                                 - init[k]))
        scale = max(r, median)
        if scale == 0.0:    # nothing active: the server must stay put
            gaps[k] = 0.0 if p == 0.0 else float("inf")
        else:
            gaps[k] = abs(p - r) / scale
    return gaps


def change_gap(program: Dict, reference: Dict, init: Dict) -> float:
    """The worst leaf's gap of :func:`leaf_gaps` (0 where none counts)."""
    worst = 0.0
    for gap in leaf_gaps(program, reference, init).values():
        worst = _worst(worst, gap)
    return worst


def _worst(a: float, b: float) -> float:
    """The larger of two readings, where one that is not a number is the
    worst of all."""
    return max(a, b) if np.isfinite(b) else float("inf")


def _same(a, b) -> bool:
    fields = ("loss", "num_active", "test_acc", "train_acc")
    return len(a) == len(b) and all(
        np.array_equal(getattr(x, f), getattr(y, f), equal_nan=True)
        for x, y in zip(a, b) for f in fields)


def same_rounds(a, b, rounds: int) -> bool:
    """Whether ``a``'s per-round rows are ``b``'s first ``rounds``,
    exactly."""
    return len(a) == len(b) and all(
        np.array_equal(getattr(x, f), getattr(y, f)[:, :rounds],
                       equal_nan=True)
        for x, y in zip(a, b) for f in ("loss", "num_active"))


def _finite(cells) -> bool:
    return all(np.isfinite(c.loss).all() and np.isfinite(c.test_acc).all()
               for c in cells)

