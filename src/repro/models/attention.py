"""Attention math, shard-local (operates on the heads a device owns).

All functions are pure jnp and engine-agnostic: the TP engines hand them
shard-local head counts.  `attend` is the dense oracle; `attend_chunked`
is the XLA flash-style query-chunked path used for long sequences (and is
the reference the Pallas flash kernel in kernels/ must match).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B,Sq,Hq,Dh), k: (B,Sk,Hkv,Dh) with Hq % Hkv == 0 ->
    scores (B,Hq,Sq,Sk) in fp32."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    q = q.reshape(b, sq, hkv, g, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    return s.reshape(b, hkv * g, sq, k.shape[1])


def _gqa_combine(p, v):
    """p: (B,Hq,Sq,Sk) fp32, v: (B,Sk,Hkv,Dh) -> (B,Sq,Hq,Dh)."""
    b, hq, sq, sk = p.shape
    hkv = v.shape[2]
    g = hq // hkv
    p = p.reshape(b, hkv, g, sq, sk)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, hq, v.shape[-1])


def causal_mask(q_pos, kv_pos, window: int = 0):
    """(..., Sq) x (..., Sk) int32 -> bool (..., Sq, Sk); True = attend."""
    m = kv_pos[..., None, :] <= q_pos[..., :, None]
    if window > 0:
        m &= kv_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m


def tree_mask(pos, anc, kv_pos):
    """Attention mask for a speculative TREE chunk (docs/speculative.md).

    The chunk's C tokens occupy DISTINCT cache slots pos..pos+C-1
    (scattered by chunk index) but sit at tree positions pos+depth
    (RoPE); visibility follows the tree, not the slot order: kv slot m
    is visible to chunk token i iff it holds committed history
    (m < pos) or an in-chunk ancestor of i (anc[i, m - pos], diagonal
    True).  pos (B,) chunk starts; anc (C, C) bool; kv_pos (B, Sk) slot
    indices.  Returns bool (B, C, Sk); True = attend.
    """
    c = anc.shape[0]
    rel = kv_pos - pos[:, None]                          # (B, Sk)
    in_chunk = (rel >= 0) & (rel < c)
    within = jnp.take(anc, jnp.clip(rel, 0, c - 1), axis=1)   # (C, B, Sk)
    within = jnp.moveaxis(within, 0, 1)                  # (B, C, Sk)
    return (rel < 0)[:, None, :] | (in_chunk[:, None, :] & within)


def attend(q, k, v, mask, scale: float | None = None):
    """Dense softmax attention oracle.

    q (B,Sq,Hq,Dh), k/v (B,Sk,Hkv,Dh), mask bool (B,Sq,Sk) or (B,1,Sq,Sk).
    Returns (B,Sq,Hq,Dh) in q.dtype.
    """
    dh = q.shape[-1]
    scale = scale if scale is not None else dh ** -0.5
    s = _gqa_scores(q * scale, k)
    if mask.ndim == 3:
        mask = mask[:, None]
    s = jnp.where(mask, s, NEG_INF)
    # guard fully-masked rows (padding) -> zero output instead of NaN
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jax.lax.stop_gradient(jnp.maximum(m, NEG_INF / 2)))
    p = jnp.where(mask, p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(denom, 1e-20)
    return _gqa_combine(p, v).astype(q.dtype)


@partial(jax.checkpoint, static_argnums=(5, 6))
def _attend_q_chunk(q, k, v, q_pos, kv_pos, window, scale):
    mask = causal_mask(q_pos, kv_pos, window)
    return attend(q, k, v, mask, scale)


def attend_chunked(q, k, v, q_pos, kv_pos, *, window: int = 0,
                   q_chunk: int = 1024, scale: float | None = None):
    """Query-chunked causal attention: O(q_chunk * Sk) score memory.

    Scans over query chunks; each chunk attends to the full K/V with a
    causal (+optional sliding window) mask built from positions.  This is
    the XLA-level flash pattern; kernels/flash_attention.py is the Pallas
    version of the same contraction.
    """
    b, sq, hq, dh = q.shape
    if sq <= q_chunk:
        return _attend_q_chunk(q, k, v, q_pos, kv_pos, window, scale)
    n = sq // q_chunk
    main = n * q_chunk
    qs = (q[:, :main].reshape(b, n, q_chunk, hq, dh)
          .transpose(1, 0, 2, 3, 4))
    ps = q_pos[:, :main].reshape(b, n, q_chunk).transpose(1, 0, 2)

    def body(_, qc):
        qi, pi = qc
        return None, _attend_q_chunk(qi, k, v, pi, kv_pos, window, scale)

    _, out = jax.lax.scan(body, None, (qs, ps))
    dv = out.shape[-1]             # MLA: v head dim != q head dim
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, main, hq, dv)
    if main < sq:   # ragged tail (e.g. a modality prefix shifts the length)
        tail = _attend_q_chunk(q[:, main:], k, v, q_pos[:, main:], kv_pos,
                               window, scale)
        out = jnp.concatenate([out, tail], axis=1)
    return out


def attention_any(q, k, v, q_pos, kv_pos, *, window: int = 0,
                  q_chunk: int = 1024, scale: float | None = None):
    """Dispatch: dense for short q, chunked for long."""
    if q.shape[1] > q_chunk:
        return attend_chunked(q, k, v, q_pos, kv_pos, window=window,
                              q_chunk=q_chunk, scale=scale)
    mask = causal_mask(q_pos, kv_pos, window)
    return attend(q, k, v, mask, scale)


# ---------------------------------------------------------------------------
# Decode-from-cache helpers
# ---------------------------------------------------------------------------

def paged_attend(q, k_pool, v_pool, page_table, pos, *,
                 scale: float | None = None, anc=None):
    """Paged-KV attention, XLA path: gather ONLY the table's pages.

    q (B,C,Hq,Dh) at absolute positions pos[b]..pos[b]+C-1; k_pool/v_pool
    (P+1, ps, Hkv, Dh) are the shared physical page pools (page P is the
    trash page); page_table (B,n) int32, -1 = unallocated (reads trash,
    fully masked).  Reuses `attend`, so numerics are bit-identical to the
    dense decode path: masked lanes contribute exactly 0.0, and
    power-of-two table widths (runtime bucketing) keep XLA's balanced
    reduction trees associating the valid prefix identically.  The fused
    Pallas kernel (kernels/ops.paged_attention) is the TPU path that
    skips even this bucketed gather.

    `anc` (C, C) bool switches the chunk to TREE visibility (tree_mask):
    the C slots at pos..pos+C-1 attend per the ancestor matrix instead
    of slot order (speculative tree verification).

    Every op it emits carries the name scope `attn` in its metadata, so a
    device trace can tell the gather and the attention math from the
    rest of the step."""
    with jax.named_scope("attn"):
        b, c = q.shape[:2]
        pn1, ps, hkv, dh = k_pool.shape
        n = page_table.shape[1]
        pt = jnp.where(page_table < 0, pn1 - 1, page_table)
        kg = jnp.take(k_pool, pt.reshape(-1),
                      axis=0).reshape(b, n * ps, hkv, dh)
        vg = jnp.take(v_pool, pt.reshape(-1),
                      axis=0).reshape(b, n * ps, hkv, dh)
        kv_pos = jnp.broadcast_to(jnp.arange(n * ps)[None], (b, n * ps))
        if anc is None:
            q_pos = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
            mask = causal_mask(q_pos, kv_pos)
        else:
            mask = tree_mask(pos, anc, kv_pos)
        mask &= (jnp.repeat(page_table, ps, axis=1) >= 0)[:, None, :]
        return attend(q, kg, vg, mask, scale)


def decode_attend(q, k_cache, v_cache, pos, *, window: int = 0,
                  scale: float | None = None):
    """Single-token decode: q (B,1,Hq,Dh); caches (B,S,Hkv,Dh);
    pos (B,) current absolute position.  For windowed layers the cache is a
    rolling buffer of size S=window (slot = p % window); validity masking
    only needs how many slots are filled, since RoPE was applied pre-cache.
    """
    b, s = k_cache.shape[0], k_cache.shape[1]
    slots = jnp.arange(s)[None, :]                      # (1,S)
    if window > 0:
        filled = jnp.minimum(pos[:, None] + 1, s)       # (B,1)
        valid = slots < filled
    else:
        valid = slots <= pos[:, None]
    mask = valid[:, None, :]                            # (B,1(Sq),S)
    return attend(q, k_cache, v_cache, mask, scale)


def cache_update(k_cache, v_cache, k_new, v_new, pos, *, window: int = 0):
    """Write one token's k/v at pos (rolling for windowed layers)."""
    slot = pos % window if window > 0 else pos          # (B,)
    b = k_cache.shape[0]
    bi = jnp.arange(b)
    k_cache = k_cache.at[bi, slot].set(k_new[:, 0])
    v_cache = v_cache.at[bi, slot].set(v_new[:, 0])
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# Int8 KV cache (beyond-paper: decode at 32k context is HBM-bound on the
# cache read; per-(pos, head) absmax scales halve the cache bytes at
# <0.5% attention-output error — tests/test_kv_int8.py)
# ---------------------------------------------------------------------------

def kv_quantize(x):
    """x (..., Dh) -> (int8 (..., Dh), scale (...,) bf16)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), -1), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def kv_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)
