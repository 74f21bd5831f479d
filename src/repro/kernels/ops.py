"""jit'd public wrappers around the Pallas kernels (padding, GQA packing,
layout plumbing).  On non-TPU backends pass interpret=True (tests) or use
the pure-XLA paths in models/ (the production CPU/GPU fallback)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as FA
from repro.kernels import fused_norm as FN
from repro.kernels import ssd_scan as SSD


def _pad_axis(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, pad)
    return jnp.pad(x, pads), pad


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, sm_scale=None, causal=True, block_q=128,
                    block_k=128, interpret=False):
    """q (B,Sq,Hq,D); k/v (B,Sk,Hkv,D) -> (B,Sq,Hq,D).

    Packs to heads-major (B*H, S, D) so the kernel's GQA index map
    (kv row = q row // group) holds, pads S to block multiples (padded
    k positions fall outside the causal mask; padded q rows are sliced
    off)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    qp = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kp = k.transpose(0, 2, 1, 3).reshape(b * hkv, k.shape[1], d)
    vp = v.transpose(0, 2, 1, 3).reshape(b * hkv, v.shape[1], d)
    qp, pq = _pad_axis(qp, 1, block_q)
    kp, pk = _pad_axis(kp, 1, block_k)
    vp, _ = _pad_axis(vp, 1, block_k)
    # padded k columns must never win: causal mask handles them only if
    # they sit AFTER every real q position — true for right padding when
    # sq == sk; for safety we rely on causal=True paths (the model's only
    # use) and assert here.
    assert causal, "non-causal padding path not needed by the model"
    out = FA.flash_attention_bhsd(qp, kp, vp, sm_scale=sm_scale,
                                  causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret)
    out = out[:, :sq].reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
    return out


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def fused_residual_rmsnorm(x, r, w, *, eps=1e-5, block_rows=256,
                           interpret=False):
    """x, r (..., d) -> (rmsnorm(x+r)*w, x+r)."""
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d)
    rf = r.reshape(-1, d)
    t = xf.shape[0]
    br = min(block_rows, t)
    xf, pad = _pad_axis(xf, 0, br)
    rf, _ = _pad_axis(rf, 0, br)
    y, s = FN.fused_residual_rmsnorm(xf, rf, w, eps=eps, block_rows=br,
                                     interpret=interpret)
    return y[:t].reshape(shape), s[:t].reshape(shape)


# ---------------------------------------------------------------------------
# Paged KV-cache attention + gather/scatter (runtime/paging.py holds the
# allocator, runtime/engines.py the wiring).  Layout contract for every
# paged leaf:
#     pool  (layer, num_pages + 1, page_size, *tail)
#     dense (layer, batch,         n * page_size, *tail)
# where page index num_pages is the TRASH page absorbing reads/writes for
# unallocated (-1) page-table entries.  Pure jnp on the non-head axes, so
# the same code runs under SimEngine's vmap and inside shard_map with the
# head tail axes sharded.
#
# Two paged attention paths (core/blocks.gqa_mixer_page dispatches):
#   * paged_attention — the fused Pallas kernel: K/V blocks are read
#     directly through the scalar-prefetched page table, no contiguous
#     materialization ever exists (attn_backend="pallas");
#   * models/attention.paged_attend — the XLA path: gathers only the
#     table's (bucketed) pages and reuses the dense attend math, so its
#     numerics are bit-identical to dense decode.
# The gather/scatter helpers below remain the fallback for archs whose
# cache trees mix paged and dense leaves (MLA latents, int8 scales,
# hybrid) — see runtime/forward.paged_decode_step.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, page_table, pos, *, sm_scale=None,
                    interpret=False):
    """Fused paged flash attention (see kernels/flash_attention.py for
    the layout contract; kernels/ref.paged_attention_ref is the oracle).

    q (B, C, Hq, D); k_pool/v_pool (P+1, ps, Hkv, D); page_table (B, n)
    int32 with -1 = unallocated; pos (B,) absolute chunk-start
    positions.  Returns (B, C, Hq, D)."""
    return FA.paged_flash_attention(q, k_pool, v_pool, page_table, pos,
                                    sm_scale=sm_scale, interpret=interpret)


def scatter_tokens_pages(pool, layer, vals, page_table, pos):
    """Write a chunk of C tokens per slot straight into its pages.

    pool (L, P+1, ps, *t) is a segment's stacked physical page pools (no
    batch axis) and `layer` the (traced) index of the layer being
    written; vals (B, C, *t) are the new entries for logical positions
    pos[b]..pos[b]+C-1 of slot b.  Positions whose table entry is -1 (or
    that fall beyond the table width — inactive slots carry garbage pos)
    land in that layer's trash page.  One vectorized scatter of B*C rows
    into the stacked pool, so a layer scan that carries the pool updates
    it in place: distinct positions of a slot never collide on (page,
    offset), distinct slots never share a live page, so only trash-page
    writes overlap (don't care)."""
    pn = pool.shape[1] - 1
    ps = pool.shape[2]
    b, c = vals.shape[:2]
    n = page_table.shape[1]
    pos2 = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None]   # (B, C)
    pidx = pos2 // ps
    phys = jnp.take_along_axis(page_table, jnp.clip(pidx, 0, n - 1), 1)
    phys = jnp.where((phys < 0) | (pidx >= n) | (pidx < 0), pn, phys)
    off = pos2 % ps
    return pool.at[layer, phys.reshape(-1), off.reshape(-1)].set(
        vals.reshape((b * c,) + vals.shape[2:]))


def gather_pages(pool, page_table):
    """pool (L, P+1, ps, *t); page_table (B, n) int32, -1 = unallocated.

    Returns the contiguous per-slot view (L, B, n*ps, *t).  Entries read
    through -1 come from the trash page; callers rely on decode position
    masking (`kv slot <= pos`) to hide them.
    """
    pn = pool.shape[1] - 1
    ps = pool.shape[2]
    b, n = page_table.shape
    pt = jnp.where(page_table < 0, pn, page_table)
    g = jnp.take(pool, pt.reshape(-1), axis=1)          # (L, B*n, ps, *t)
    return g.reshape(pool.shape[:1] + (b, n * ps) + pool.shape[3:])


def scatter_token_page(pool, dense, page_table, pos):
    """Write back the ONE token decode just produced per slot.

    dense (L, B, n*ps, *t) is the post-update contiguous view; the entry
    at sequence index pos[b] is the token written this step.  It lands in
    physical page page_table[b, pos[b]//ps] at offset pos[b]%ps; slots
    with no page mapped (-1) write to the trash page.
    """
    pn = pool.shape[1] - 1
    ps = pool.shape[2]
    b = page_table.shape[0]
    phys = jnp.take_along_axis(page_table, (pos // ps)[:, None], 1)[:, 0]
    phys = jnp.where(phys < 0, pn, phys)
    tok = dense[:, jnp.arange(b), pos]                  # (L, B, *t)
    return pool.at[:, phys, pos % ps].set(tok)


def scatter_chunk_pages(pool, dense, page_table, pos, n: int):
    """Write back the `n` tokens a verify forward just produced per slot.

    dense (L, B, n_pages*ps, *t) holds the post-update contiguous view;
    the entries at sequence indices pos[b]..pos[b]+n-1 are the tokens
    written this step (speculative verify scores n = k+1 tokens at
    once).  One vectorized scatter over all B*n tokens: distinct
    positions of a slot never collide on (page, offset) and distinct
    slots never share a live page, so only trash-page writes (unmapped
    -1 entries, inactive slots) overlap — harmlessly."""
    pn = pool.shape[1] - 1
    ps = pool.shape[2]
    b, npg = page_table.shape
    pos2 = pos[:, None] + jnp.arange(n, dtype=jnp.int32)[None]   # (B, n)
    pidx = pos2 // ps
    phys = jnp.take_along_axis(page_table, jnp.clip(pidx, 0, npg - 1), 1)
    phys = jnp.where((phys < 0) | (pidx >= npg) | (pidx < 0), pn, phys)
    toks = dense[:, jnp.arange(b)[:, None], pos2]          # (L, B, n, *t)
    toks = toks.reshape((dense.shape[0], b * n) + dense.shape[3:])
    return pool.at[:, phys.reshape(-1), (pos2 % ps).reshape(-1)].set(toks)


def scatter_prefill_pages(pool, dense1, page_row):
    """Insert one request's prefill cache into its allocated pages.

    dense1 (L, 1, S, *t) with S == len(page_row) * ps (the per-slot
    maximum); page_row (pages_per_slot,) int32.  Pages the slot did not
    allocate (-1) scatter into the trash page, so the right-padded tail of
    the prefill cache never touches live pages.
    """
    pn = pool.shape[1] - 1
    ps = pool.shape[2]
    d = dense1[:, 0]                                     # (L, S, *t)
    n = d.shape[1] // ps
    d = d.reshape(d.shape[:1] + (n, ps) + d.shape[2:])   # (L, n, ps, *t)
    phys = jnp.where(page_row[:n] < 0, pn, page_row[:n])
    return pool.at[:, phys].set(d)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, bm, cm, dd, *, chunk=128, interpret=False):
    """Batched heads: x (B,S,H,P), dt (B,S,H), a (H,), bm/cm (B,S,G,N)
    with G == 1 or G == H, dd (H,) -> y (B,S,H,P)."""
    b, s, h, p = x.shape
    g = bm.shape[2]
    n = bm.shape[-1]
    xp = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtp = dt.transpose(0, 2, 1).reshape(b * h, s)
    if g == 1:
        bmp = jnp.broadcast_to(bm.transpose(0, 2, 1, 3), (b, h, s, n))
    else:
        bmp = bm.transpose(0, 2, 1, 3)
    bmp = bmp.reshape(b * h, s, n)
    if g == 1:
        cmp_ = jnp.broadcast_to(cm.transpose(0, 2, 1, 3), (b, h, s, n))
    else:
        cmp_ = cm.transpose(0, 2, 1, 3)
    cmp_ = cmp_.reshape(b * h, s, n)
    ap = jnp.tile(a, b)
    ddp = jnp.tile(dd, b)
    ck = min(chunk, s)
    assert s % ck == 0, (s, ck)
    y = SSD.ssd_scan(xp, dtp, ap, bmp, cmp_, ddp, chunk=ck,
                     interpret=interpret)
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
