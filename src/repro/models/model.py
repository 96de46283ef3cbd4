"""Model assembly for all assigned architecture families.

Design:
- Parameters are plain nested dicts (pytrees); everything is functional.
- The layer stack is organised in *periods*: the smallest repeating pattern of
  layer kinds (jamba: 8 = 7 mamba + 1 attn; gemma2: 2 = local+global; VLM: 5 =
  4 self + 1 cross; llama4: 2 = dense+MoE; plain dense: 1). Period parameters
  are stacked on a leading axis and the stack is ``lax.scan``-ed, so the HLO
  holds one period regardless of depth — essential for compiling 88-100 layer
  configs against a 512-device mesh.
- ``forward``  : train/prefill, full-sequence.
- ``decode_step``: one token against a KV/SSM/RWKV cache (serve path).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import attention, cross_attention, decode_attention
from repro.models.layers import (
    apply_rope,
    dtype_of,
    init_dense,
    mlp_apply,
    mlp_init,
    rms_norm,
    rope_angles,
    softcap,
)
from repro.models.flags import scan_unroll
from repro.models.moe import moe_apply, moe_init
from repro.sharding.specs import maybe_constrain


# ---------------------------------------------------------------------------
# Period structure
# ---------------------------------------------------------------------------


def period_length(cfg: ModelConfig) -> int:
    p = 1
    if cfg.family == "hybrid":
        p = math.lcm(max(cfg.attn_every, 1), max(cfg.moe_every, 1) if cfg.moe else 1)
    elif cfg.family == "vlm" and cfg.cross_attn_every:
        p = cfg.cross_attn_every
    elif cfg.attention.pattern == "local_global":
        p = 2
    if cfg.moe and cfg.family != "hybrid":
        p = math.lcm(p, max(cfg.moe_every, 1))
    assert cfg.num_layers % p == 0, (cfg.name, cfg.num_layers, p)
    return p


def attn_kind(cfg: ModelConfig, layer_idx: int) -> str:
    pat = cfg.attention.pattern
    if pat == "local_global":
        return "swa" if layer_idx % 2 == 0 else "full"
    return pat


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _attn_init(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.head_dim
    a = cfg.attention
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], d, a.num_heads * hd, dt),
        "wk": init_dense(ks[1], d, a.num_kv_heads * hd, dt),
        "wv": init_dense(ks[2], d, a.num_kv_heads * hd, dt),
        "wo": init_dense(ks[3], a.num_heads * hd, d, dt),
    }


def _layer_init(key, cfg: ModelConfig, layer_idx: int):
    kind = cfg.layer_kind(layer_idx)
    dt = dtype_of(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((d,), dt)}
    if kind == "rwkv":
        p["tmix"] = rwkv_mod.rwkv_init(ks[0], cfg)
        p["ln2"] = jnp.zeros((d,), dt)
        return p  # channel-mix params live inside tmix dict (ck/cv/cr)
    if kind == "ssm":
        p["ssm"] = ssm_mod.ssm_init(ks[0], cfg)
    else:
        p["attn"] = _attn_init(ks[0], cfg)
    if kind == "cross":
        p["lnc"] = jnp.zeros((d,), dt)
        p["cross"] = _attn_init(ks[1], cfg)
        # VLM: zero-init gate (Llama-3.2 style); enc-dec: open gate
        gate0 = 2.0 if cfg.family == "audio" else 0.0
        p["cross_gate"] = jnp.asarray(gate0, jnp.float32)
    p["ln2"] = jnp.zeros((d,), dt)
    if cfg._is_moe_layer(layer_idx):
        p["moe"] = moe_init(ks[2], cfg)
    else:
        p["ffn"] = mlp_init(ks[2], cfg)
    return p


def init_params(key, cfg: ModelConfig):
    P = period_length(cfg)
    n_periods = cfg.num_layers // P
    dt = dtype_of(cfg)
    k_emb, k_blocks, k_head, k_enc, k_extra = jax.random.split(key, 5)

    def period_init(k):
        kk = jax.random.split(k, P)
        return tuple(_layer_init(kk[i], cfg, i) for i in range(P))

    params = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model), jnp.float32)
                  * 0.02).astype(dt),
        "blocks": jax.vmap(period_init)(jax.random.split(k_blocks, n_periods)),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(k_head, cfg.d_model, cfg.vocab_size, dt)
    if cfg.family == "vlm":
        params["image_proj"] = init_dense(k_extra, cfg.d_model, cfg.d_model, dt)
    if cfg.family == "audio":
        def enc_layer_init(k):
            k1, k2 = jax.random.split(k)
            return {
                "ln1": jnp.zeros((cfg.d_model,), dt),
                "attn": _attn_init(k1, cfg),
                "ln2": jnp.zeros((cfg.d_model,), dt),
                "ffn": mlp_init(k2, cfg),
            }
        params["encoder"] = jax.vmap(enc_layer_init)(
            jax.random.split(k_enc, cfg.encoder_layers))
        params["enc_norm"] = jnp.zeros((cfg.d_model,), dt)
        params["audio_proj"] = init_dense(k_extra, cfg.d_model, cfg.d_model, dt)
    return params


def master_config(cfg: ModelConfig) -> ModelConfig:
    """``cfg`` with float32 parameters: what ``init_params`` of it gives are
    the float32 master weights that federated state holds. Every forward
    pass below computes in ``cfg.dtype`` from a cast of them
    (:func:`compute_params`)."""
    return dataclasses.replace(cfg, dtype="float32")


@functools.lru_cache(maxsize=None)
def _compute_dtypes(cfg: ModelConfig):
    return jax.tree.map(lambda s: s.dtype, jax.eval_shape(
        partial(init_params, cfg=cfg), jax.random.PRNGKey(0)))


def compute_params(params, cfg: ModelConfig):
    """``params`` in the dtypes ``init_params(key, cfg)`` gives each leaf:
    the compute copy of float32 master weights (named ``fed.cast`` in the
    compiled program), differentiable back to the masters. Params already
    in those dtypes pass through untouched, so a program that holds them
    in ``cfg.dtype`` is unchanged."""
    dtypes = _compute_dtypes(cfg)
    if all(x.dtype == d for x, d in zip(jax.tree.leaves(params),
                                         jax.tree.leaves(dtypes))):
        return params
    with jax.named_scope("fed.cast"):
        return jax.tree.map(lambda x, d: x.astype(d), params, dtypes)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _self_attn_block(p, x, cfg, kind, positions):
    a = cfg.attention
    hd = cfg.head_dim
    b, t, _ = x.shape
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q = (h @ p["attn"]["wq"]).reshape(b, t, a.num_heads, hd)
    k = (h @ p["attn"]["wk"]).reshape(b, t, a.num_kv_heads, hd)
    v = (h @ p["attn"]["wv"]).reshape(b, t, a.num_kv_heads, hd)
    cos, sin = rope_angles(positions, hd, a.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    with jax.named_scope("lm.attn"):
        o = attention(q, k, v, kind=kind, window=a.window,
                      logit_softcap=a.logit_softcap)
    return x + o.reshape(b, t, -1) @ p["attn"]["wo"]


def _cross_block(p, x, cfg, memory):
    a = cfg.attention
    hd = cfg.head_dim
    b, t, _ = x.shape
    h = rms_norm(x, p["lnc"], cfg.norm_eps)
    q = (h @ p["cross"]["wq"]).reshape(b, t, a.num_heads, hd)
    k = (memory @ p["cross"]["wk"]).reshape(b, memory.shape[1], a.num_kv_heads, hd)
    v = (memory @ p["cross"]["wv"]).reshape(b, memory.shape[1], a.num_kv_heads, hd)
    o = cross_attention(q, k, v)
    gate = jnp.tanh(p["cross_gate"]).astype(x.dtype)
    return x + gate * (o.reshape(b, t, -1) @ p["cross"]["wo"])


def _ffn_block(p, x, cfg):
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        out, aux = moe_apply(p["moe"], h, cfg)
        return x + out, aux
    with jax.named_scope("lm.mlp"):
        out = mlp_apply(p["ffn"], h, cfg.gated_mlp)
    return x + out, jnp.zeros((), jnp.float32)


def _apply_layer(p, x, cfg, layer_idx, positions, memory):
    kind = cfg.layer_kind(layer_idx)
    if kind == "rwkv":
        h, _ = rwkv_mod.rwkv_time_mix(p["tmix"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg)
        x = x + h
        h, _ = rwkv_mod.rwkv_channel_mix(p["tmix"], rms_norm(x, p["ln2"], cfg.norm_eps))
        return x + h, jnp.zeros((), jnp.float32)
    if kind == "ssm":
        h, _ = ssm_mod.ssm_apply(p["ssm"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg)
        x = x + h
        return _ffn_block(p, x, cfg)
    x = _self_attn_block(p, x, cfg, attn_kind(cfg, layer_idx), positions)
    if kind == "cross":
        x = _cross_block(p, x, cfg, memory)
    return _ffn_block(p, x, cfg)


def _encode_audio(params, cfg, frames):
    x = frames @ params["audio_proj"]
    positions = jnp.arange(frames.shape[1])

    def body(h, lp):
        h = _self_attn_block(
            {"ln1": lp["ln1"], "attn": lp["attn"]}, h, cfg, "full", positions)
        h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
        return h + mlp_apply(lp["ffn"], h2, cfg.gated_mlp), None

    x, _ = jax.lax.scan(lambda h, lp: body(h, lp), x, params["encoder"],
                        unroll=scan_unroll())
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, tokens, *, memory=None, remat=False):
    """tokens [B, T] -> logits [B, T, V].

    ``memory``: image patch embeddings [B, I, D] (vlm), audio frame
    embeddings [B, F, D] (audio) — the stubbed modality frontends.
    ``remat``: checkpoint each period (training path) so the scan saves only
    the residual carries, not per-layer attention/FFN intermediates.
    Float32 master ``params`` are cast to ``cfg.dtype`` first
    (:func:`compute_params`).
    """
    params = compute_params(params, cfg)
    x = params["embed"][tokens].astype(dtype_of(cfg))
    positions = jnp.arange(tokens.shape[1])
    if cfg.family == "vlm":
        memory = memory @ params["image_proj"]
    elif cfg.family == "audio":
        memory = _encode_audio(params, cfg, memory)
    P = period_length(cfg)

    def period_body(carry, block):
        x, aux = carry
        x = maybe_constrain(x)  # sequence-parallel residual (no-op w/o mesh ctx)
        for i in range(P):
            x, a = _apply_layer(block[i], x, cfg, i, positions, memory)
            aux = aux + a
        return (maybe_constrain(x), aux), None

    if remat:
        period_body = jax.checkpoint(period_body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(period_body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"], unroll=scan_unroll())
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head", None)
    with jax.named_scope("lm.head"):
        logits = (x @ head) if head is not None else (x @ params["embed"].T)
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, aux


def hidden_forward(params, cfg: ModelConfig, tokens, *, memory=None, remat=False):
    """Like ``forward`` but stops before the LM head: returns (hidden, aux)."""
    params = compute_params(params, cfg)
    x = params["embed"][tokens].astype(dtype_of(cfg))
    positions = jnp.arange(tokens.shape[1])
    if cfg.family == "vlm":
        memory = memory @ params["image_proj"]
    elif cfg.family == "audio":
        memory = _encode_audio(params, cfg, memory)
    P = period_length(cfg)

    def period_body(carry, block):
        x, aux = carry
        x = maybe_constrain(x)
        for i in range(P):
            x, a = _apply_layer(block[i], x, cfg, i, positions, memory)
            aux = aux + a
        return (maybe_constrain(x), aux), None

    if remat:
        period_body = jax.checkpoint(period_body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(period_body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"], unroll=scan_unroll())
    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def loss_fn(params, cfg: ModelConfig, batch, *, remat=True, ce_chunk=512):
    """batch: {'tokens': [B,T], 'labels': [B,T], optional 'memory'}.

    Cross-entropy is computed in token chunks under jax.checkpoint so the
    [B, T, V] logits tensor (tens of GB at 128k-256k vocab) never fully
    materializes — only one [B, ce_chunk, V] chunk is live at a time.
    Float32 master ``params`` are cast to ``cfg.dtype`` here, so the
    gradient comes back in float32.
    """
    params = compute_params(params, cfg)
    hidden, aux = hidden_forward(params, cfg, batch["tokens"],
                                 memory=batch.get("memory"), remat=remat)
    with jax.named_scope("lm.head"):
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"].T
        ce = _chunked_ce(hidden, head, batch["labels"], cfg, ce_chunk)
    return ce + 0.01 * aux


def _chunked_ce(hidden, head, labels, cfg: ModelConfig, ce_chunk: int):
    """Mean next-token cross-entropy of ``hidden @ head`` against
    ``labels``, ``ce_chunk`` positions at a time (see :func:`loss_fn`)."""
    b, t, d = hidden.shape

    def chunk_ce(h_chunk, y_chunk):
        logits = softcap((h_chunk @ head).astype(jnp.float32), cfg.final_softcap)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y_chunk[..., None], axis=-1)[..., 0]
        return (lse - gold).sum()

    chunk_ce = jax.checkpoint(chunk_ce, prevent_cse=False)
    ce_chunk = min(ce_chunk, t)
    n = -(-t // ce_chunk)
    pad = n * ce_chunk - t
    hp = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0))) if pad else hidden
    yp = jnp.pad(labels, ((0, 0), (0, pad))) if pad else labels
    # padded labels index 0 against padded (zero) hidden rows: their CE is a
    # constant log(V) offset; mask by weighting
    hc = hp.reshape(b, n, ce_chunk, d).transpose(1, 0, 2, 3)
    yc = yp.reshape(b, n, ce_chunk).transpose(1, 0, 2)

    def body(tot, xs):
        h_, y_ = xs
        return tot + chunk_ce(h_, y_), None

    if pad:
        # simplest correct handling: compute full-seq in one chunk when padded
        logits = softcap((hidden @ head).astype(jnp.float32), cfg.final_softcap)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return (lse - gold).mean()
    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, yc),
                          unroll=scan_unroll())
    return tot / (b * t)


# ---------------------------------------------------------------------------
# Decode (serve path)
# ---------------------------------------------------------------------------


def make_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Cache pytree matching the period structure (leading axis = n_periods).

    Windowed layers (swa / chunked) get a ring buffer of size ``window``,
    which is what bounds long_500k decode memory for mixtral/gemma2/llama4."""
    P = period_length(cfg)
    n_periods = cfg.num_layers // P
    a = cfg.attention
    hd = cfg.head_dim
    dt = dtype_of(cfg)

    def layer_cache(i):
        kind = cfg.layer_kind(i)
        if kind == "rwkv":
            nh = cfg.d_model // cfg.rwkv.head_dim
            return {
                "s": jnp.zeros((n_periods, batch, nh, cfg.rwkv.head_dim, cfg.rwkv.head_dim), jnp.float32),
                "last": jnp.zeros((n_periods, batch, 1, cfg.d_model), dt),
                "clast": jnp.zeros((n_periods, batch, 1, cfg.d_model), dt),
            }
        if kind == "ssm":
            st = ssm_mod.ssm_init_state(cfg, batch)
            return jax.tree.map(lambda x: jnp.zeros((n_periods,) + x.shape, x.dtype), st)
        eff = max_len
        if attn_kind(cfg, i) in ("swa", "chunked"):
            eff = min(max_len, a.window)
        return {
            "k": jnp.zeros((n_periods, batch, eff, a.num_kv_heads, hd), dt),
            "v": jnp.zeros((n_periods, batch, eff, a.num_kv_heads, hd), dt),
        }

    return tuple(layer_cache(i) for i in range(P))


def _decode_attn_layer(p, x, cfg, kind, cache, pos):
    """One-token self-attention against cache; returns (x, new_cache)."""
    a = cfg.attention
    hd = cfg.head_dim
    b = x.shape[0]
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q = (h @ p["attn"]["wq"]).reshape(b, 1, a.num_heads, hd)
    k = (h @ p["attn"]["wk"]).reshape(b, 1, a.num_kv_heads, hd)
    v = (h @ p["attn"]["wv"]).reshape(b, 1, a.num_kv_heads, hd)
    cos, sin = rope_angles(pos[None], hd, a.rope_theta)
    q = apply_rope(q, cos[None], sin[None])
    k = apply_rope(k, cos[None], sin[None])
    s_max = cache["k"].shape[1]
    slot = pos % s_max if kind in ("swa", "chunked") else jnp.minimum(pos, s_max - 1)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    if kind in ("swa", "chunked"):
        # Ring buffer of size `window`. Make it chronological (oldest first):
        # once full, the oldest entry sits at slot+1.
        eff_len = jnp.minimum(pos + 1, s_max)
        shift = jnp.where(pos + 1 >= s_max, -(slot + 1), 0)
        # chunked attends only within the current block: last (pos%window)+1
        # tokens; swa attends the whole (<= window) ring.
        keep = (pos % a.window) + 1 if kind == "chunked" else eff_len
        keep = jnp.minimum(keep, eff_len)
        drop = eff_len - keep
        ckl = jnp.roll(ck, shift - drop, axis=1)
        cvl = jnp.roll(cv, shift - drop, axis=1)
        o = decode_attention(q, ckl, cvl, keep, kind="full",
                             logit_softcap=a.logit_softcap)
    else:
        o = decode_attention(q, ck, cv, pos + 1, kind=kind, window=a.window,
                             logit_softcap=a.logit_softcap)
    x = x + o.reshape(b, 1, -1) @ p["attn"]["wo"]
    return x, {"k": ck, "v": cv}


def decode_step(params, cfg: ModelConfig, token, cache, pos, *, memory=None):
    """token [B, 1] int32; cache from make_cache; pos scalar int32 (= tokens
    already in cache). Returns (logits [B, 1, V], new_cache)."""
    params = compute_params(params, cfg)
    x = params["embed"][token].astype(dtype_of(cfg))
    if cfg.family == "vlm":
        memory = memory @ params["image_proj"]
    elif cfg.family == "audio":
        memory = _encode_audio(params, cfg, memory)
    P = period_length(cfg)

    def period_body(x, xs):
        block, pcache = xs
        new_pcache = []
        for i in range(P):
            p = block[i]
            c = pcache[i]
            kind = cfg.layer_kind(i)
            if kind == "rwkv":
                h = rms_norm(x, p["ln1"], cfg.norm_eps)
                o, st = rwkv_mod.rwkv_time_mix(
                    p["tmix"], h, cfg, state={"s": c["s"], "last": c["last"]})
                x = x + o
                h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
                o, clast = rwkv_mod.rwkv_channel_mix(p["tmix"], h2, state=c["clast"])
                x = x + o
                new_pcache.append({"s": st["s"], "last": st["last"], "clast": clast})
            elif kind == "ssm":
                h = rms_norm(x, p["ln1"], cfg.norm_eps)
                o, st = ssm_mod.ssm_apply(p["ssm"], h, cfg, state=c)
                x = x + o
                x, _ = _ffn_block(p, x, cfg)
                new_pcache.append(st)
            else:
                x, nc = _decode_attn_layer(p, x, cfg, attn_kind(cfg, i), c, pos)
                if kind == "cross":
                    x = _cross_block(p, x, cfg, memory)
                x, _ = _ffn_block(p, x, cfg)
                new_pcache.append(nc)
        return x, tuple(new_pcache)

    x, new_cache = jax.lax.scan(period_body, x, (params["blocks"], cache),
                                unroll=scan_unroll())
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head", None)
    logits = (x @ head) if head is not None else (x @ params["embed"].T)
    return softcap(logits.astype(jnp.float32), cfg.final_softcap), new_cache
