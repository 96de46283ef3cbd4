"""Float32 master weights under bfloat16 compute.

Federated state holds float32 masters whatever the client model's compute
dtype; aggregation sums, divides and stores in float32. The regression
these pin: a masked mean of a few clients' bfloat16 parameters rounded back
to the bfloat16 grid, which moves updates of about one ulp by as much as
the updates themselves (smollm-135m's server change read 0.94 against a
float32 reference where three clients were active)."""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FederationConfig, get_config, reduced
from repro.core import make_link_process, make_round_fn
from repro.core.algorithms import AlgorithmSpec, masked_mean
from repro.core.federated import init_fed_state, local_steps
from repro.experiments.tasks import eval_chunk, next_token_accuracy
from repro.models import model as lm
from repro.optim import sgd

M, N = 4, 4096
ACTIVE = jnp.asarray([True, True, False, True])


def _one_ulp_updates():
    """A float32 server and four clients' bfloat16 models, each the server
    moved by about one bfloat16 ulp (the grid step at the weight's
    magnitude) up or down: what clients computing in bfloat16 hand over."""
    rng = np.random.default_rng(0)
    w = (0.04 * rng.standard_normal(N)).astype(np.float32)
    ulp = 2.0 ** (np.floor(np.log2(np.abs(w))) - 7)
    step = rng.choice([-1.0, 1.0], size=(M, N)) * ulp
    x = jnp.asarray(w + step, jnp.bfloat16)
    return jnp.asarray(w), x


def _float32_mean_change(w, x):
    act = np.asarray(ACTIVE)
    xs = np.asarray(x.astype(jnp.float32), np.float64)[act]
    return xs.mean(0) - np.asarray(w, np.float64)


def _relative_error(change, want):
    return np.linalg.norm(change - want) / np.linalg.norm(want)


def test_masked_mean_of_bf16_clients_is_float32():
    w, x = _one_ulp_updates()
    mean = masked_mean({"w": x}, ACTIVE)["w"]
    assert mean.dtype == jnp.float32
    change = np.asarray(mean, np.float64) - np.asarray(w, np.float64)
    assert _relative_error(change, _float32_mean_change(w, x)) < 1e-4


@pytest.mark.parametrize("algo", ["fedpbc", "fedavg"])
@pytest.mark.parametrize("traced", [False, True])
def test_server_change_is_the_float32_mean(algo, traced):
    """3 active clients of 4, updates of about one bfloat16 ulp: the
    server's change is the float32 mean of theirs, through the family's
    switch (traced ``algo_id``) and through a bound member alike. Rounded
    to the bfloat16 grid, the same mean is off by about its own size."""
    w, x = _one_ulp_updates()
    spec = AlgorithmSpec(("fedpbc", "fedavg"))
    aid = spec.id_of(algo)

    def agg(aid):
        state = spec.init({"w": w}, M)
        _, server, clients = spec.aggregate(
            aid, state, {"w": w}, {"w": x.astype(jnp.float32)}, {"w": x},
            ACTIVE, jnp.full((M,), 0.5), jnp.int32(0))
        return server["w"], clients["w"]

    server, clients = jax.jit(agg)(jnp.int32(aid)) if traced else agg(aid)
    assert server.dtype == jnp.float32
    change = np.asarray(server, np.float64) - np.asarray(w, np.float64)
    want = _float32_mean_change(w, x)
    assert _relative_error(change, want) < 1e-4
    rounded = np.asarray(jnp.asarray(w + want, jnp.bfloat16), np.float64) - w
    assert _relative_error(rounded, want) > 0.1


def test_bf16_compute_round_keeps_float32_masters():
    """One round of a bfloat16-compute transformer's clients: server,
    clients and optimizer state stay float32, and the server's change is
    the float32 mean of the active clients' trained masters."""
    cfg = dataclasses.replace(reduced(get_config("smollm-135m"), d_model=32,
                                      layers=1), dtype="bfloat16")
    fed = FederationConfig(algorithm="fedavg", num_clients=M, local_steps=1)
    spec = AlgorithmSpec(("fedpbc", "fedavg"))
    link = make_link_process(jnp.full((M,), 0.7), fed)
    opt = sgd(0.1)
    params = lm.init_params(jax.random.PRNGKey(0), lm.master_config(cfg))
    state = init_fed_state(jax.random.PRNGKey(1), params, fed, spec, link, opt)
    seqs = jax.random.randint(jax.random.PRNGKey(2), (M, 1, 2, 17), 0,
                              cfg.vocab_size)
    batches = {"tokens": seqs[..., :-1], "labels": seqs[..., 1:]}

    def loss(p, b):
        return lm.loss_fn(p, cfg, b, remat=False, ce_chunk=16)

    round_fn = make_round_fn(loss, opt, spec, link, fed,
                             algo_id=spec.id_of("fedavg"))
    new, mets = jax.jit(round_fn)(state, batches)
    held = (new.server, new.clients, new.opt_state)
    assert {str(x.dtype) for x in jax.tree.leaves(held)
            if jnp.issubdtype(x.dtype, jnp.floating)} == {"float32"}
    active = np.asarray(mets["active"])
    assert active.any()
    # the trained masters, from the same start (fedavg broadcasts the
    # server), by the engine's own local steps
    starts = jax.tree.map(lambda x: jnp.broadcast_to(x, (M,) + x.shape),
                          state.server)
    trained, _, _ = jax.jit(jax.vmap(partial(local_steps, loss, opt, s=1)))(
        starts, state.opt_state, batches)
    for got, x, w in zip(jax.tree.leaves(new.server), jax.tree.leaves(trained),
                         jax.tree.leaves(state.server)):
        want = np.asarray(x, np.float64)[active].mean(0) - np.asarray(w)
        change = np.asarray(got, np.float64) - np.asarray(w)
        # the server rounds the mean to float32 once: under 1e-3 of a
        # change of about half a bfloat16 ulp
        if np.linalg.norm(want) > 0:
            assert _relative_error(change, want) < 1e-3


# float32 logits of one 512-token sequence over the 49,152-token vocabulary
# take 96 MiB: two sequences fit an eval chunk, and a 64-wide vocabulary's
# whole corpus does
@pytest.mark.parametrize("n,seq,vocab,want", [(16, 512, 49152, 2),
                                              (64, 512, 49152, 2),
                                              (256, 32, 512, 256),
                                              (15, 512, 49152, 1)])
def test_eval_chunk_fits_the_logits_budget(n, seq, vocab, want):
    assert eval_chunk(n, seq, vocab) == want


@pytest.mark.parametrize("chunk", [1, 2, 4, 8])
def test_chunked_eval_equals_unchunked(chunk):
    """Next-token accuracy over a corpus in chunks of sequences is the
    one-pass accuracy, exactly: correct predictions are counted, then
    divided once."""
    cfg = dataclasses.replace(reduced(get_config("smollm-135m"), d_model=32,
                                      layers=1), dtype="float32")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    # a corpus the model half predicts: every other sequence repeats a
    # token, so accuracy is neither 0 nor 1 and miscounts would show
    seqs = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                              cfg.vocab_size)
    seqs = seqs.at[::2].set(seqs[::2, :1])

    def forward(tokens):
        return lm.forward(params, cfg, tokens)[0]

    whole = jax.jit(lambda s: next_token_accuracy(forward, s, 8))(seqs)
    part = jax.jit(lambda s: next_token_accuracy(forward, s, chunk))(seqs)
    assert float(part) == float(whole)
