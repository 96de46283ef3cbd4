"""Plain float32 reference of the smollm-family cell (``smollm-135m``).

The SmolLM block (HuggingFaceTB/SmolLM-135M, a Llama-style decoder) and
one trajectory of the federated simulation around it, written out from
the model card's description and the simulator's documented seed
protocol, importing nothing of the program (the data split and the Eq.-9
uplink draw come from the paper-protocol reference,
:mod:`bench.refs.paper_mlp`, which imports nothing of it either):

- the model: token embedding, ``layers`` decoder blocks (RMSNorm, RoPE on
  the two halves of each head, grouped-query causal attention with
  ``kv_heads`` key/value heads shared by ``heads // kv_heads`` query heads
  each, a residual add, RMSNorm, a SwiGLU feed-forward, a residual add), a
  final RMSNorm and the head tied to the embedding; the loss is the mean
  next-token cross-entropy, its gradient ``jax.grad`` of that;
- the corpus: ``n`` styled sequences of ``seq + 1`` tokens from the data
  seed, each style drawing uniformly from its half-vocabulary window;
- per round: a Bernoulli uplink mask from the time-varying Eq.-9
  ``p_i^t``, each client's mini-batches of sequences drawn with
  replacement from its shard, ``s`` local SGD steps with
  ``eta_t = eta_0 / sqrt(t/10 + 1)``, and the server rule (FedPBC's
  postponed broadcast, FedAvg).

Everything is float32 with every product at ``Precision.HIGHEST``
(:data:`SOUND`). :class:`Numerics` also states the program's numerics and
the controls' in the same code: the compute dtype of the forward and
backward passes, the dtype parameters are held and updated in, and the
dtype the server's mean is summed in.

Departures from the published description, each shared with the program:

- RMSNorm scales by ``1 + w`` with ``w`` initialized to zero (the
  published checkpoint stores ``w`` near one and scales by ``w``): the
  same function, parametrized from zero;
- weights are random: embedding ``N(0, 0.02^2)``, every projection
  ``N(0, 1/fan_in)``, norms zero, as the simulator initializes them;
- RMSNorm's epsilon is 1e-6 (the published config says 1e-5) and RoPE's
  base 10,000, as the simulator's config states them;
- attention is computed in blocks of query positions so that the scores of
  a whole batch never live at once; each layer and each block of the
  cross-entropy is recomputed in the backward pass (``jax.checkpoint``),
  which changes no arithmetic.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs import paper_mlp

F32 = jnp.float32
NEG_INF = -1e30


@dataclass(frozen=True)
class Arch:
    """The widths of the model (the published ones for the cell)."""

    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6


@dataclass(frozen=True)
class Numerics:
    """Where float32 gives way to bfloat16: the forward and backward
    passes (``compute``), the parameters as held and updated (``master``),
    the server's masked mean (``mean``). ``"float32"`` or ``"bfloat16"``
    each."""

    compute: str = "float32"
    master: str = "float32"
    mean: str = "float32"


SOUND = Numerics()
# the program's numerics, the parent program's, and float32 masters with
# the parent's bfloat16 mean
PROGRAM = Numerics(compute="bfloat16")
CONTROLS = {
    "bf16_master": Numerics("bfloat16", "bfloat16", "bfloat16"),
    "bf16_mean": Numerics("bfloat16", "float32", "bfloat16"),
}


# --------------------------------------------------------------------------
# corpus
# --------------------------------------------------------------------------


def styled_corpus(rng: np.random.Generator, n: int, seq: int, vocab: int,
                  styles: int):
    """``n`` sequences of ``seq + 1`` tokens and their styles: style ``c``
    draws uniformly from ``[c V / (2 styles), c V / (2 styles) + V/2)``."""
    style = rng.integers(0, styles, size=n).astype(np.int32)
    lo = (style * (vocab // 2)) // max(styles, 1)
    toks = lo[:, None] + rng.integers(0, vocab // 2, size=(n, seq + 1))
    return toks.astype(np.int32), style


def corpus(data_seed: int, *, n: int, n_test: int, seq: int, vocab: int,
           styles: int) -> Dict[str, np.ndarray]:
    """Train and held-out sequences, one generator from the data seed."""
    rng = np.random.default_rng(data_seed)
    toks, style = styled_corpus(rng, n, seq, vocab, styles)
    toks_t, _ = styled_corpus(rng, n_test, seq, vocab, styles)
    return {"toks": toks, "style": style, "toks_t": toks_t}


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------


def _dense(key, d_in, d_out):
    return jax.random.normal(key, (d_in, d_out), F32) * d_in ** -0.5


def _layer(key, a: Arch):
    k_attn, _, k_ffn, _ = jax.random.split(key, 4)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    ku, kd, kg = jax.random.split(k_ffn, 3)
    q, kvw = a.heads * a.head_dim, a.kv_heads * a.head_dim
    return {"ln1": jnp.zeros((a.d_model,), F32),
            "wq": _dense(kq, a.d_model, q), "wk": _dense(kk, a.d_model, kvw),
            "wv": _dense(kv, a.d_model, kvw), "wo": _dense(ko, q, a.d_model),
            "ln2": jnp.zeros((a.d_model,), F32),
            "up": _dense(ku, a.d_model, a.d_ff),
            "down": _dense(kd, a.d_ff, a.d_model),
            "gate": _dense(kg, a.d_model, a.d_ff)}


@partial(jax.jit, static_argnames=("arch",))
def init_params(key, arch: Arch) -> Dict[str, jnp.ndarray]:
    """Float32 weights from ``key`` by the simulator's key protocol; the
    layers' leaves are stacked on a leading ``[layers]`` axis."""
    k_emb, k_blocks, _, _, _ = jax.random.split(key, 5)
    layers = jax.vmap(lambda k: _layer(jax.random.split(k, 1)[0], arch))(
        jax.random.split(k_blocks, arch.layers))
    return {"embed": jax.random.normal(k_emb, (arch.vocab, arch.d_model),
                                       F32) * 0.02,
            "final_norm": jnp.zeros((arch.d_model,), F32),
            **{f"blocks.{k}": v for k, v in layers.items()}}


# the program's pytree paths of the leaves above
PROGRAM_PATHS = {
    "embed": ("embed",), "final_norm": ("final_norm",),
    "blocks.ln1": ("blocks", 0, "ln1"), "blocks.ln2": ("blocks", 0, "ln2"),
    "blocks.wq": ("blocks", 0, "attn", "wq"),
    "blocks.wk": ("blocks", 0, "attn", "wk"),
    "blocks.wv": ("blocks", 0, "attn", "wv"),
    "blocks.wo": ("blocks", 0, "attn", "wo"),
    "blocks.up": ("blocks", 0, "ffn", "up"),
    "blocks.down": ("blocks", 0, "ffn", "down"),
    "blocks.gate": ("blocks", 0, "ffn", "gate"),
}


def from_program(tree) -> Dict[str, np.ndarray]:
    """The program's parameter pytree as this module's flat leaves."""
    out = {}
    for name, path in PROGRAM_PATHS.items():
        x = tree
        for k in path:
            x = x[k]
        out[name] = np.asarray(x, np.float32)
    return out


def _mm(a, b):
    return jnp.matmul(a, b, precision="highest")


def _rms_norm(x, w, eps):
    xf = x.astype(F32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return ((1.0 + w.astype(F32)) * out).astype(x.dtype)


def _rope(x, theta, positions):
    """Rotate the two halves of each head by position (``[b, t, h, d]``)."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v, block: int):
    """Causal softmax attention, ``[b, t, h, d]``, in float32 from the
    operands, ``block`` query positions at a time; key/value heads are
    shared by consecutive groups of query heads."""
    b, t, h, d = q.shape
    block = min(block, t)
    rep = h // k.shape[2]
    k = jnp.repeat(k.astype(F32), rep, axis=2)
    v = jnp.repeat(v.astype(F32), rep, axis=2)
    qb = q.astype(F32).reshape(b, t // block, block, h, d)

    @jax.checkpoint
    def one(i, qi):
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision="highest")
        s = s * d ** -0.5
        allow = (i * block + jnp.arange(block))[:, None] >= jnp.arange(t)
        s = jnp.where(allow, s, NEG_INF)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")

    out = jax.lax.map(lambda x: one(*x),
                      (jnp.arange(t // block), qb.transpose(1, 0, 2, 3, 4)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, d).astype(q.dtype)


def _block(x, p, a: Arch, block: int):
    b, t, _ = x.shape
    pos = jnp.arange(t)
    h = _rms_norm(x, p["ln1"], a.norm_eps)
    q = _rope(_mm(h, p["wq"]).reshape(b, t, a.heads, a.head_dim), a.rope_theta,
              pos)
    k = _rope(_mm(h, p["wk"]).reshape(b, t, a.kv_heads, a.head_dim),
              a.rope_theta, pos)
    v = _mm(h, p["wv"]).reshape(b, t, a.kv_heads, a.head_dim)
    o = _attention(q, k, v, block)
    x = x + _mm(o.reshape(b, t, -1), p["wo"])
    h = _rms_norm(x, p["ln2"], a.norm_eps)
    return x + _mm(jax.nn.silu(_mm(h, p["gate"])) * _mm(h, p["up"]), p["down"])


def loss(params, tokens, labels, arch: Arch, compute: str = "float32",
         block: int = 128, ce_block: int = 128):
    """Mean next-token cross-entropy of ``tokens [b, t]`` against
    ``labels``: the weights cast to ``compute`` first, the logits and the
    loss in float32."""
    p = jax.tree.map(lambda w: w.astype(compute), params)
    x = p["embed"][tokens]
    layers = {k[len("blocks."):]: v for k, v in p.items()
              if k.startswith("blocks.")}
    step = jax.checkpoint(lambda x, lp: (_block(x, lp, arch, block), None))
    x, _ = jax.lax.scan(step, x, layers)
    x = _rms_norm(x, p["final_norm"], arch.norm_eps)
    b, t, d = x.shape

    @jax.checkpoint
    def ce(xs):
        h, y = xs
        logits = _mm(h, p["embed"].T).astype(F32)
        gold = jnp.take_along_axis(logits, y[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    ce_block = min(ce_block, t)
    n = t // ce_block
    parts = jax.lax.map(ce, (x.reshape(b, n, ce_block, d).swapaxes(0, 1),
                             labels.reshape(b, n, ce_block).swapaxes(0, 1)))
    return jnp.sum(parts) / (b * t)


# --------------------------------------------------------------------------
# one trajectory
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Protocol:
    """The shapes and link dynamics every trajectory of a cell shares."""

    arch: Arch
    m: int
    local_steps: int
    batch: int
    per_client: int
    gamma: float = 0.5
    period: float = 40.0
    attn_block: int = 128
    ce_block: int = 128


def _p_t(p_base, r, proto: Protocol):
    wave = jnp.sin(2.0 * jnp.pi * r / proto.period)
    return jnp.clip(p_base * ((1.0 - proto.gamma) + proto.gamma * wave),
                    0.0, 1.0)


def active_counts(seed: int, p_base, rounds: int,
                  proto: Protocol) -> np.ndarray:
    """Active uplinks per round: ``uniform(k_link, [m]) < p_i^t``, the
    link key split off the state key ``split(PRNGKey(seed + 2))[1]`` each
    round."""
    key = jax.random.split(jax.random.PRNGKey(seed + 2))[1]
    out = []
    for r in range(rounds):
        key, k_link = jax.random.split(key)
        active = jax.random.uniform(k_link, (proto.m,)) < _p_t(p_base, r,
                                                               proto)
        out.append(int(active.sum()))
    return np.asarray(out)


def _server_rule(algo, server, x_star, active, mean_dtype):
    """The masked mean of the active clients (summed in ``mean_dtype``,
    stored in the server's dtype), kept where nobody is active."""
    count = jnp.sum(active.astype(F32))
    mask = active.astype(mean_dtype)

    def mean(x, s):
        xs = x.astype(mean_dtype) * mask.reshape((-1,) + (1,) * (x.ndim - 1))
        agg = (xs.sum(0) / jnp.maximum(count, 1.0).astype(mean_dtype))
        return jnp.where(count > 0, agg.astype(s.dtype), s)

    return jax.tree.map(mean, x_star, server)


@partial(jax.jit, static_argnames=("algo", "proto", "numerics"))
def _round(carry, r, toks, idx, p_base, lr, data_key, *, algo, proto,
           numerics):
    """One synchronous round over all m clients."""
    server, clients, key = carry
    a, m, s = proto.arch, proto.m, proto.local_steps
    pick = jax.random.randint(jax.random.fold_in(data_key, r),
                              (m, s, proto.batch), 0, proto.per_client)
    seqs = toks[idx[jnp.arange(m)[:, None, None], pick]]
    key, k_link = jax.random.split(key)
    active = jax.random.uniform(k_link, (m,)) < _p_t(p_base, r, proto)
    params = clients if algo == "fedpbc" else jax.tree.map(
        lambda x: jnp.broadcast_to(x, (m,) + x.shape), server)

    def f(p, seq):
        return loss(p, seq[:, :-1], seq[:, 1:], a, numerics.compute,
                    proto.attn_block, proto.ce_block)

    grad = jax.vmap(jax.value_and_grad(f))
    total = jnp.zeros((m,), F32)
    for j in range(s):
        value, g = grad(params, seqs[:, j])
        eta = lr / jnp.sqrt((r * s + j).astype(F32) / 10.0 + 1.0)
        params = jax.tree.map(
            lambda p, d: (p.astype(F32) - eta * d.astype(F32)).astype(p.dtype),
            params, g)
        total = total + value
    new_server = _server_rule(algo, server, params, active, numerics.mean)
    tiled = jax.tree.map(lambda x: jnp.broadcast_to(x, (m,) + x.shape),
                         new_server)
    if algo == "fedpbc":        # postponed broadcast: active clients only
        new_clients = jax.tree.map(
            lambda t, x: jnp.where(
                active.reshape((-1,) + (1,) * (x.ndim - 1)), t, x),
            tiled, params)
    else:
        new_clients = tiled
    return (new_server, new_clients, key), (jnp.mean(total / s),
                                            jnp.sum(active))


def follow(algo: str, lr: float, seed: int, proto: Protocol, toks, idx,
           p_base, rounds: int, numerics: Numerics = SOUND
           ) -> Dict[str, object]:
    """One trajectory's first ``rounds`` rounds: its per-round mean client
    loss and active count, the server it starts from (``init``) and the one
    it holds after the last of them (``server``)."""
    with jax.default_matmul_precision("highest"):
        init = init_params(jax.random.PRNGKey(seed + 1), proto.arch)
        server = jax.tree.map(lambda x: x.astype(numerics.master), init)
        clients = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (proto.m,) + x.shape), server)
        carry = (server, clients,
                 jax.random.split(jax.random.PRNGKey(seed + 2))[1])
        data_key = jax.random.PRNGKey(seed + 4)
        toks, idx = jnp.asarray(toks), jnp.asarray(idx, jnp.int32)
        step = partial(_round, algo=algo, proto=proto, numerics=numerics)
        losses, counts = [], []
        for r in range(rounds):
            carry, (l, c) = step(carry, jnp.int32(r), toks, idx, p_base,
                                 jnp.asarray(lr, F32), data_key)
            losses.append(float(l))
            counts.append(int(c))
    return {"loss": np.asarray(losses), "num_active": np.asarray(counts),
            "init": {k: np.asarray(v, np.float64) for k, v in init.items()},
            "server": {k: np.asarray(v, np.float32)
                       for k, v in carry[0].items()}}


def split(data_seed: int, style: np.ndarray, m: int, alpha: float,
          per_client: int) -> np.ndarray:
    """Each client's style mixture ~ Dirichlet(alpha), ``per_client``
    sequences each: ``[m, per_client]`` corpus indices."""
    return paper_mlp.dirichlet_split(data_seed, style, m, alpha, per_client)


def uplink_probs(seed: int, m: int, styles: int, *, alpha: float,
                 sigma0: float, delta: float):
    """Eq. 9 over the corpus's styles (:func:`paper_mlp.uplink_probs`)."""
    return paper_mlp.uplink_probs(seed, m, styles, alpha=alpha,
                                  sigma0=sigma0, delta=delta)

