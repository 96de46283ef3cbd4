"""Production federated-training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --rounds 50 --clients 8 --algorithm fedpbc --scheme bernoulli

Runs the FedPBC round engine over the selected architecture on the local
devices: ``--reduced`` (the default) cuts the model to the CPU-sized
``reduced()`` variant in float32, ``--full`` keeps every published width
and computes in the config's own dtype — e.g. the 30-layer, 135M-parameter
smollm-135m in bfloat16 on one TPU v5e chip (``chip_smoke.py`` runs that
path). Either way the federated state holds float32 master weights, which
each client's loss casts to the compute dtype, as the sweep engine does.

Rounds execute on the scanned engine (``repro.core.make_run_rounds``): token
batches are sampled on device by ``repro.data.lm_source`` and every
log/checkpoint interval runs as ONE dispatch (``jax.lax.scan`` over the round
function), instead of one dispatch + host batch upload per round.
Checkpoints carry the full ``{fed, ds}`` state every --ckpt-every rounds, so
a restore resumes mid-sweep with the identical trajectory.

``build`` and ``train`` are the two halves of ``main`` for callers that need
the round program itself (to lower it, or to take one client's gradient)
before training with it.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Callable, List

import numpy as np


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--algorithm", default="fedpbc")
    ap.add_argument("--scheme", default="bernoulli",
                    choices=["bernoulli", "markov", "cyclic"])
    ap.add_argument("--time-varying", action="store_true")
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


@dataclasses.dataclass
class Trainer:
    """One launcher run's model config, round program and initial state."""

    cfg: Any
    loss: Callable          # (params, batch) -> scalar
    source: Any             # the on-device DataSource
    run_rounds: Callable    # jitted (state, ds_state, key, num_rounds)
    state: Any              # FedState
    ds_state: Any
    data_key: Any


def build(args: argparse.Namespace) -> Trainer:
    import jax
    import jax.numpy as jnp

    from repro.configs import FederationConfig, get_config, reduced
    from repro.core import (
        build_base_probs,
        init_fed_state,
        make_algorithm,
        make_link_process,
        make_run_rounds,
    )
    from repro.data import lm_source
    from repro.models.model import init_params, loss_fn, master_config
    from repro.optim import paper_decay, sgd

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(reduced(cfg), dtype="float32")
    print(f"arch={cfg.name} family={cfg.family} params~"
          f"{cfg.param_count() / 1e6:.1f}M reduced={args.reduced}")

    m = args.clients
    fed = FederationConfig(algorithm=args.algorithm, num_clients=m,
                           local_steps=args.local_steps, scheme=args.scheme,
                           time_varying=args.time_varying)
    p, _, _ = build_base_probs(jax.random.PRNGKey(args.seed), m, 10,
                               alpha=0.1, sigma0=4.0, delta=0.05)
    print("client uplink probabilities:", np.asarray(p).round(3))
    algo = make_algorithm(fed)
    link = make_link_process(jnp.asarray(p), fed)
    opt = sgd(paper_decay(args.lr))

    # at published widths every layer is recomputed in the backward pass:
    # the float32 client masters leave no room for the clients' saved
    # activations (8 clients of smollm-135m need 16.0 GiB without it)
    def loss(params, batch):
        return loss_fn(params, cfg, batch, remat=not args.reduced)

    if cfg.family == "vlm":
        memory_shape = (args.batch, cfg.num_image_tokens, cfg.d_model)
    elif cfg.family == "audio":
        memory_shape = (args.batch, cfg.num_audio_frames, cfg.d_model)
    else:
        memory_shape = None
    source = lm_source(num_clients=m, local_steps=args.local_steps,
                       batch=args.batch, seq=args.seq, vocab=cfg.vocab_size,
                       memory_shape=memory_shape)

    run_rounds = make_run_rounds(loss, opt, algo, link, fed, source)
    params = init_params(jax.random.PRNGKey(args.seed + 1),
                         master_config(cfg))
    st = init_fed_state(jax.random.PRNGKey(args.seed + 2), params, fed,
                        algo, link, opt)
    ds_state = source.init(jax.random.PRNGKey(args.seed + 3))
    return Trainer(cfg=cfg, loss=loss, source=source, run_rounds=run_rounds,
                   state=st, ds_state=ds_state,
                   data_key=jax.random.PRNGKey(args.seed + 4))


def train(tr: Trainer, args: argparse.Namespace) -> List[dict]:
    """Run ``args.rounds`` rounds (resuming from ``--ckpt-dir``); returns one
    log entry per scanned chunk: ``{"round", "loss" [chunk], "active"}``.
    The trainer's state buffers are donated on the chip: ``tr`` holds the
    final state afterwards."""
    from repro.checkpointing import latest_step, restore, save

    m = args.clients
    st, ds_state = tr.state, tr.ds_state
    if args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            try:
                st, ds_state = restore(args.ckpt_dir, last, (st, ds_state))
            except (KeyError, AssertionError) as e:
                raise SystemExit(
                    f"checkpoint {args.ckpt_dir}/ckpt_{last:08d}.npz does not "
                    "match the current (FedState, ds_state) layout — likely a "
                    "pre-scan-engine checkpoint (FedState only) or a different "
                    f"--arch/--clients setting. Delete or move --ckpt-dir to "
                    f"start fresh. ({e})")
            print(f"restored round {int(st.round)} from {args.ckpt_dir}")

    def next_boundary(t: int) -> int:
        """Next log or checkpoint boundary after round t (scan chunk end)."""
        nxt = min(t - t % args.log_every + args.log_every, args.rounds)
        if args.ckpt_dir:
            nxt = min(nxt, t - t % args.ckpt_every + args.ckpt_every)
        return nxt

    log = []
    t0 = time.time()
    start_round = t = int(st.round)
    while t < args.rounds:
        chunk = next_boundary(t) - t
        st, ds_state, mets = tr.run_rounds(st, ds_state, tr.data_key, chunk)
        tr.state, tr.ds_state = st, ds_state
        t += chunk
        losses = np.asarray(mets["loss"])
        log.append({"round": t, "loss": losses,
                    "active": int(mets["num_active"][-1])})
        print(f"round {t:4d} loss {float(losses[-1]):.4f} "
              f"active {log[-1]['active']}/{m} "
              f"mean_staleness {float(np.mean(mets['staleness'][-1])):.1f} "
              f"({(time.time() - t0):.1f}s)", flush=True)
        if args.ckpt_dir and t % args.ckpt_every == 0:
            save(args.ckpt_dir, t, (st, ds_state))
    print(f"done: {args.rounds - start_round} rounds in {time.time() - t0:.1f}s")
    return log


def main(argv=None) -> List[dict]:
    args = parse_args(argv)
    return train(build(args), args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
