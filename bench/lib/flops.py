"""Operations and bytes that serving work needs, from a model's `Arch`.

Adapted from the program's `benchmarks/analytic.py` (2·N FLOPs per token
for the matmuls plus the attention term), with two differences: the
attention term counts the context lengths the requests really have, and
the bytes are what the work needs (the weight shard plus the keys and
values of those lengths), not what an implementation happens to move.
So a decode step that gathers a whole power-of-two page table still
needs only the bytes of its real contexts, and its roofline share says
how far it is from that."""
from __future__ import annotations

from typing import Iterable

from bench.lib.decoder import Arch


def layer_matmul_params(a: Arch) -> int:
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    return a.d * (q + 2 * kv) + q * a.d + (3 if a.gated else 2) * a.d * a.ff


def param_count(a: Arch) -> int:
    """Weights of the matrices (the program's `ModelConfig.param_count`
    convention: embedding, head when untied, and every layer's
    projections; no norms, biases or position table)."""
    return (a.vocab * a.d * (1 if a.tied else 2)
            + a.layers * layer_matmul_params(a))


def kv_bytes_per_token(a: Arch, dtype_bytes: int = 2) -> int:
    return a.layers * 2 * a.kv_heads * a.head_dim * dtype_bytes


def weight_bytes_per_chip(a: Arch, tp: int, dtype_bytes: int = 2) -> float:
    """Bytes of weights one decode step has to read on each chip: the
    chip's shard of every split matrix and bias, every replicated norm
    and bias whole, and the vocabulary shard of the head.  The token
    embedding is read by rows only, unless it is the head."""
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    split = layer_matmul_params(a)
    rep = 2 * a.d * (2 if a.norm == "layer" else 1)
    if a.bias:
        split += q + 2 * kv + a.ff
        rep += 2 * a.d
    if a.qk_norm:
        rep += 2 * a.head_dim
    lnf = a.d * (2 if a.norm == "layer" else 1)
    head = a.vocab * a.d
    return dtype_bytes * (a.layers * (split / tp + rep) + lnf + head / tp)


def decode_flops(a: Arch, contexts: Iterable[int]) -> float:
    """One decode step, one row per context: the matmuls and head for
    each row, and attention of its one query over its `ctx` keys."""
    n, attn = 0, 0.0
    for c in contexts:
        n += 1
        attn += 4.0 * c * a.heads * a.head_dim * a.layers
    return 2.0 * (a.layers * layer_matmul_params(a) + a.vocab * a.d) * n \
        + attn


def decode_bytes_per_chip(a: Arch, tp: int, contexts: Iterable[int],
                          dtype_bytes: int = 2) -> float:
    """One decode step on one chip: its weight shard once, the keys and
    values of every row's real context, and each row's new key and
    value."""
    contexts = list(contexts)
    kv = kv_bytes_per_token(a, dtype_bytes) / tp
    return (weight_bytes_per_chip(a, tp, dtype_bytes)
            + kv * (sum(contexts) + len(contexts)))


def prefill_flops(a: Arch, prompt: int) -> float:
    """One prompt of `prompt` tokens: every token through the layers,
    causal attention over the tokens before it, and the head once (only
    the last position's logits are used)."""
    attn = 4.0 * a.heads * a.head_dim * a.layers * prompt * (prompt + 1) / 2
    return (2.0 * a.layers * layer_matmul_params(a) * prompt
            + 2.0 * a.vocab * a.d + attn)
