"""Language-model sweep cells: the program's ``run_sweep`` over an LM grid.

The configuration file names the client model (``arch``, at the widths
its ``model`` section states, which must be the program's own) and the
corpus and Eq.-9 settings (``sizes``); the traffic file gives the grid:
algorithms, link scheme, clients, local steps, per-client batch, context
length, learning rates, how many seeds, rounds and eval cadence. One
window step is one ``run_sweep`` call over the whole grid; its work is the
number of trajectory-rounds it simulates. The window holds nothing of a
call's device state: at published widths a call's ``[B, m, P]`` float32
client stacks fill most of the chip.

Correctness, after the window: one more ``run_sweep`` call over the first
``loss_rounds`` rounds, whose rows must be the window call's first rounds
exactly, and whose server parameters are read from the program's batched
runner as the call leaves them (:class:`bench.drivers.sweep.Capture`).
Every trajectory is followed by the plain float32 reference
(:mod:`bench.refs.smollm_135m`) through those rounds: the mean client loss
to a relative gap (``loss_gap``) and the norm of each leaf's change of the
server parameters (``change_gap``, :func:`bench.drivers.sweep.change_gap`).
The active-client count of every round of the window call must be the
reference's link draw's, exactly (``active_mismatch``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
from typing import Dict, List

import numpy as np

from bench import lm_flops
from bench.drivers import sweep as base
from bench.refs import smollm_135m as ref

UNIT = "traj_rounds_per_s"

# the configuration's ``model`` keys (as the model's own config.json names
# them) and the program's ModelConfig fields they must equal
WIDTHS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "tie_word_embeddings": "tie_embeddings", "rms_norm_eps": "norm_eps"}
ATTENTION = {"num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
             "rope_theta": "rope_theta"}


def program_config(config: Dict):
    """The program's model configuration for ``config["arch"]``, checked
    against the widths the configuration file states."""
    from repro.configs import get_config

    cfg = get_config(config["arch"])
    want = config["model"]
    have = {k: getattr(cfg, f) for k, f in WIDTHS.items()}
    have.update({k: getattr(cfg.attention, f) for k, f in ATTENTION.items()})
    wrong = {k: (want[k], have[k]) for k in have if want[k] != have[k]}
    if wrong:
        raise RuntimeError(f"the program's {config['arch']} is not the "
                           f"configuration's: {wrong}")
    return cfg


def arch_of(config: Dict) -> ref.Arch:
    w = config["model"]
    return ref.Arch(layers=w["num_hidden_layers"], d_model=w["hidden_size"],
                    d_ff=w["intermediate_size"],
                    heads=w["num_attention_heads"],
                    kv_heads=w["num_key_value_heads"],
                    head_dim=w["head_dim"], vocab=w["vocab_size"],
                    rope_theta=w["rope_theta"], norm_eps=w["rms_norm_eps"])


class Workload(base.Workload):
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        import jax

        from repro.experiments import SweepSpec

        self.jax = jax
        self.config, self.traffic = config, traffic
        program_config(config)
        self.arch = arch_of(config)
        sz = config["sizes"]
        self.spec = SweepSpec(
            algorithms=tuple(traffic["algorithms"]),
            schemes=(traffic["scheme"],),
            seeds=tuple(seed + i for i in range(traffic["seeds_per_run"])),
            lrs=tuple(traffic["lrs"]),
            rounds=traffic["rounds"], eval_every=traffic["eval_every"],
            num_clients=traffic["clients"],
            local_steps=traffic["local_steps"],
            batch_size=traffic["batch"], data_seed=seed,
            classes=sz["styles"], per_client=sz["per_client"],
            alpha=sz["alpha"], sigma0=sz["sigma0"], delta=sz["delta"],
            gamma=sz["gamma"], task="lm", lm_arch=config["arch"],
            lm_reduced=False, lm_seq=traffic["seq"],
            lm_n_seqs=sz["n_seqs"], lm_n_test=sz["n_test"],
            fed_overrides=(("period", sz["period"]),))
        n = traffic["loss_rounds"]
        self.early_spec = dataclasses.replace(self.spec, rounds=n,
                                              eval_every=n)
        self.trajectories = (len(self.spec.algorithms)
                             * len(self.spec.hparam_points())
                             * len(self.spec.seeds))
        self.work_per_step = self.trajectories * self.spec.rounds
        self.first = self.last = self.sound = None
        self.failed = 0

    def _ctx(self):
        """The program computes in the configuration's dtype at the
        platform's default matrix-product precision."""
        return contextlib.nullcontext()

    def client_state_bytes(self) -> int:
        """Bytes of the client stacks a call holds on the device: B
        trajectories x m clients x the parameters of the program's master
        weights (``B * m * P * 4`` for float32 masters)."""
        from repro.experiments import grid

        task = grid.get_traced_task(self.spec)
        shapes = self.jax.eval_shape(task.init_params,
                                     self.jax.random.PRNGKey(0))
        per_client = sum(x.size * x.dtype.itemsize
                         for x in self.jax.tree.leaves(shapes))
        return self.trajectories * self.spec.num_clients * per_client

    def prepare(self) -> None:
        """Compiles (or loads) and runs the grid once; that call's rows are
        what every later call must repeat."""
        print(f"client_state_bytes={self.client_state_bytes()}",
              file=sys.stderr, flush=True)
        self.first = self.last = self._call()

    def release(self) -> None:
        """Nothing of the window's calls is left on the device but what
        Python may still have to collect."""
        gc.collect()

    def server_of_last_call(self, cells) -> List[Dict[str, np.ndarray]]:
        """The server parameters the captured runner call ended with, one
        trajectory per row as :func:`ref.from_program`'s flat leaves, after
        checking that it is the one runner call made since
        :func:`base.capture_runners` and that its metrics are ``cells``'s
        rows; the runners then drop what they hold."""
        from repro.experiments import grid

        runners = [r for r in grid._RUNNER_CACHE.values()
                   if isinstance(r, base.Capture)]
        live = [r.last for r in runners if r.last is not None]
        if len(live) != 1:
            raise RuntimeError(f"expected one runner call, found {len(live)}")
        states, out = live[0]
        for r in runners:
            r.last = None
        loss = np.asarray(out["metrics"]["loss"])
        if not np.array_equal(loss, np.concatenate([c.loss for c in cells]),
                              equal_nan=True):
            raise RuntimeError("the runner's last call is not the rows'")
        server = ref.from_program(states.server)
        del states, out
        gc.collect()
        return [{k: v[b] for k, v in server.items()}
                for b in range(len(loss))]

    # -- correctness -------------------------------------------------------

    def proto(self) -> ref.Protocol:
        s, sz = self.spec, self.config["sizes"]
        return ref.Protocol(arch=self.arch, m=s.num_clients,
                            local_steps=s.local_steps, batch=s.batch_size,
                            per_client=sz["per_client"],
                            gamma=sz["gamma"], period=float(sz["period"]))

    def reference(self, cells, numerics: ref.Numerics = ref.SOUND
                  ) -> List[Dict]:
        """The reference's run of every trajectory of ``cells`` over the
        first ``loss_rounds`` rounds at ``numerics``, with the active counts
        of all of the call's rounds, in the rows' order."""
        s, sz = self.spec, self.config["sizes"]
        data = ref.corpus(s.data_seed, n=sz["n_seqs"], n_test=sz["n_test"],
                          seq=s.lm_seq, vocab=self.arch.vocab,
                          styles=sz["styles"])
        idx = ref.split(s.data_seed, data["style"], s.num_clients,
                        sz["alpha"], sz["per_client"])
        proto = self.proto()
        out = []
        for cell in cells:
            for seed in cell.seeds:
                p = ref.uplink_probs(seed, s.num_clients, sz["styles"],
                                     alpha=sz["alpha"], sigma0=sz["sigma0"],
                                     delta=sz["delta"])
                run = ref.follow(cell.algo, cell.hparams["lr"], seed, proto,
                                 data["toks"], idx, p,
                                 self.early_spec.rounds, numerics)
                run["active_all"] = ref.active_counts(seed, p, s.rounds,
                                                      proto)
                out.append(run)
        return out

    def rows(self, cells) -> List[Dict]:
        """The program's rounds of every trajectory of the last window
        call, with the server parameters of one more call over its first
        ``loss_rounds`` rounds, whose rows must be the window call's first
        rounds exactly."""
        n = self.early_spec.rounds
        early_cells, early = self.call_servers(self.early_spec)
        if not base.same_rounds(early_cells, cells, n):
            raise RuntimeError(f"the call over {n} rounds does not repeat "
                               f"the window call's first {n}")
        return [{"loss": cell.loss[j], "num_active": cell.num_active[j],
                 "server": e}
                for (cell, j, _), e in zip(self.trajectories_of(cells),
                                           early)]

    def trajectories_of(self, cells):
        for cell in cells:
            for j, seed in enumerate(cell.seeds):
                yield cell, j, (cell.algo, cell.hparams["lr"], seed)

    def compare(self, rows: List[Dict], refs: List[Dict]) -> Dict[str, float]:
        """Worst relative loss gap and worst leaf's change gap over the
        first ``loss_rounds`` rounds; the count of the call's rounds whose
        active clients differ."""
        n = self.early_spec.rounds
        gap, change, active = 0.0, 0.0, 0
        for p, r in zip(rows, refs):
            loss = np.asarray(p["loss"][:n], np.float64)
            gap = base._worst(gap, float(np.max(
                np.abs(loss - r["loss"]) / np.abs(r["loss"]))))
            change = base._worst(change, base.change_gap(
                p["server"], r["server"], r["init"]))
            active += int(np.sum(np.asarray(p["num_active"])
                                 != r["active_all"]))
        return {"loss_gap": gap, "change_gap": change,
                "active_mismatch": float(active)}

    def check(self) -> Dict[str, float]:
        self.sound = self.reference(self.last)
        return self.compare(self.rows(self.last), self.sound)

    def control(self, name: str) -> Dict[str, float]:
        """A control's readings: the reference at the numerics
        :data:`ref.CONTROLS` names in the program's place, against the
        sound reference of the last :meth:`check` (or a new one; the grid's
        layout comes from one program call)."""
        if self.first is None:
            self.prepare()
        if self.sound is None:
            self.sound = self.reference(self.first)
        as_rows = [{"loss": r["loss"], "num_active": r["active_all"],
                    "server": r["server"]}
                   for r in self.reference(self.first, ref.CONTROLS[name])]
        return self.compare(as_rows, self.sound)

    # -- per-layer inputs --------------------------------------------------

    def train_flops_per_unit(self) -> float:
        s = self.spec
        return lm_flops.train_flops_per_traj_round(
            self.arch, seq=s.lm_seq, clients=s.num_clients,
            local_steps=s.local_steps, batch=s.batch_size)

    def flash_sequences_per_call(self) -> int:
        """Sequences the flash attention kernel runs over in one call,
        each through every layer: every client's local steps (twice where
        each layer is recomputed in the backward pass), the in-scan evals
        and the eager eval of the train corpus, for every trajectory."""
        s = self.spec
        train = (s.rounds * s.num_clients * s.local_steps * s.batch_size
                 * (2 if self.config["sizes"]["remat"] else 1))
        evals = s.lm_n_test * (s.rounds // s.eval_every) + s.lm_n_seqs
        return self.trajectories * (train + evals)
