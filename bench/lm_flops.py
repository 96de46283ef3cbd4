"""Operations and bytes of the language-model cells' work, from shapes.

``arch`` is a :class:`bench.refs.smollm_135m.Arch` (layers, widths, heads,
vocabulary). Training FLOPs count the forward pass, the backward pass
(twice the forward's products) and the SGD update, and nothing
recomputed: no layer recomputed in the backward pass, no masked half of
causal attention.
"""
from __future__ import annotations


def params(arch) -> int:
    """Parameters of the decoder with its head tied to the embedding."""
    d, q, kv = arch.d_model, arch.heads * arch.head_dim, \
        arch.kv_heads * arch.head_dim
    layer = 2 * d + d * q + 2 * d * kv + q * d + 3 * d * arch.d_ff
    return arch.layers * layer + arch.vocab * d + d


def forward_flops_per_sequence(arch, seq: int) -> float:
    """Products of one sequence's forward pass: every projection, the
    feed-forward, the head, and causal attention's scores and weighted sum
    over the ``seq (seq + 1) / 2`` allowed query-key pairs of each head."""
    d, q, kv = arch.d_model, arch.heads * arch.head_dim, \
        arch.kv_heads * arch.head_dim
    dense = arch.layers * (d * q + 2 * d * kv + q * d + 3 * d * arch.d_ff) \
        + d * arch.vocab
    return 2.0 * dense * seq + arch.layers * attention_flops(arch, seq)


def attention_flops(arch, seq: int) -> float:
    """One layer's causal attention over one sequence: ``q k^T`` and
    ``p v``, 2 flops a multiply-add, each head."""
    return 2 * 2.0 * arch.heads * arch.head_dim * seq * (seq + 1) / 2


def attention_bytes(arch, seq: int, itemsize: int = 2) -> float:
    """The least one layer's attention over one sequence moves: each
    head's queries, keys and values read once and its output written once
    (the kernel's grouped key/value heads come repeated per query head)."""
    return 4.0 * arch.heads * seq * arch.head_dim * itemsize


def train_flops_per_traj_round(arch, *, seq: int, clients: int,
                               local_steps: int, batch: int) -> float:
    """One trajectory-round: every client's ``local_steps`` SGD steps over
    ``batch`` sequences of ``seq`` tokens."""
    per_step = 3 * forward_flops_per_sequence(arch, seq) * batch \
        + 2 * params(arch)
    return float(clients * local_steps * per_step)


def attention_roofline_seconds(arch, seq: int, peak_flops: float,
                               peak_bytes_per_s: float, itemsize: int = 2):
    """The least time one layer's attention over one sequence takes on a
    device: ``(seconds, bound)`` with ``bound`` ``"flops"`` or ``"bytes"``,
    whichever takes longer."""
    compute = attention_flops(arch, seq) / peak_flops
    memory = attention_bytes(arch, seq, itemsize) / peak_bytes_per_s
    return (compute, "flops") if compute >= memory else (memory, "bytes")
