"""Causal flash attention — Pallas TPU kernel.

TPU-native adaptation (not a CUDA port): the online-softmax accumulators
live in VMEM scratch; the grid is (batch*q_heads, q_blocks, k_blocks)
with the k dimension minor-most — TPU grids execute sequentially over the
minor dimension, so scratch carries (m, l, acc) across k blocks and the
output is finalized on the last one.  Block shapes default to 128×128,
matching the MXU systolic tile; GQA is handled in the BlockSpec index
maps (the kv block for q-head h comes from kv-head h // group — no
materialized head broadcast in HBM).

Validated on CPU via interpret=True against ref.py (tests sweep shapes
and dtypes); the model's XLA path (models/attention.py) is the same
contraction and serves as the non-TPU fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  sm_scale: float, block_q: int, block_k: int, causal: bool,
                  n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (bq, d)
    k = k_ref[0].astype(jnp.float32)               # (bk, d)
    v = v_ref[0].astype(jnp.float32)               # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                               # (bq, bk)
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = rows >= cols
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (bq, 1)
    m_cur = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=1))
    corr = jnp.exp(m_prev[:, 0] - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * corr[:, None] + jnp.sum(p, axis=1)[:, None]
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_ref[...] = m_cur[:, None]

    @pl.when(ki == n_k - 1)
    def _final():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _paged_flash_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *, sm_scale: float,
                        n_pages: int, trash: int):
    """One grid step per (slot, query block, logical page).

    The page table and chunk-start positions arrive as scalar-prefetch
    refs: BlockSpec index maps read `pt_ref` to pick WHICH physical K/V
    page the next block fetch targets, so unallocated entries never move
    bytes beyond the one trash page.  The online-softmax accumulators
    (acc, m, l) live in VMEM scratch carried across the minor (pages)
    grid dimension; heads ride as the leading batch of a 3-d dot_general
    so GQA needs no materialized head broadcast in HBM."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (bc, Hq, D)
    k = k_ref[0].astype(jnp.float32)               # (ps, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    c, hq, d = q.shape
    ps, hkv, _ = k.shape
    g = hq // hkv
    # heads-as-batch: q (Hq, bc, D) x k (Hq, ps, D) -> s (Hq, bc, ps)
    qt = q.transpose(1, 0, 2)
    kt = jnp.repeat(k.transpose(1, 0, 2), g, axis=0)
    vt = jnp.repeat(v.transpose(1, 0, 2), g, axis=0)
    s = jax.lax.dot_general(qt, kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
    # causal mask over absolute positions + trash mask for -1 entries
    # (the caller maps -1 -> trash before prefetch; `== trash` recovers
    # the sign since no real table entry can equal the trash index)
    qpos = (pos_ref[b] + qi * c
            + jax.lax.broadcasted_iota(jnp.int32, (hq, c, ps), 1))
    kvpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (hq, c, ps), 2)
    valid = (kvpos <= qpos) & (pt_ref[b * n_pages + j] != trash)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...].reshape(hq, c)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[..., None])
    p = jnp.where(valid, p, 0.0)
    l_new = l_ref[...].reshape(hq, c) * corr + jnp.sum(p, axis=2)
    acc = acc_ref[...].reshape(hq, c, d)
    acc = acc * corr[..., None] + jax.lax.dot_general(
        p, vt, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur.reshape(hq * c, 1)
    l_ref[...] = l_new.reshape(hq * c, 1)
    acc_ref[...] = acc.reshape(hq * c, d)

    @pl.when(j == n_pages - 1)
    def _final():
        # fully-masked rows (e.g. inactive slots) divide by the guard and
        # produce zeros instead of NaN, matching the XLA attend path
        denom = jnp.maximum(l_ref[...], 1e-20)
        o = (acc_ref[...] / denom).reshape(hq, c, d).transpose(1, 0, 2)
        o_ref[0] = o.astype(o_ref.dtype)


#: query rows (heads x chunk positions) one paged-kernel grid step holds:
#: its fp32 scratch and (Hq, bc, ps) intermediates stay within the TPU's
#: 16 MiB scoped VMEM (a 256-token chunk at 16 heads does not)
PAGED_Q_ROWS = 512


def _query_block(c: int, hq: int) -> int:
    """Largest divisor of the chunk length with hq * block <= the row
    budget (1 when even one position per head exceeds it)."""
    bc = max(1, min(c, PAGED_Q_ROWS // hq))
    while c % bc:
        bc -= 1
    return bc


def paged_flash_attention(q, k_pool, v_pool, page_table, pos, *,
                          sm_scale=None, interpret=False):
    """Paged-KV causal flash attention reading K/V through a page table.

    q (B, C, Hq, D): per-slot query chunk at absolute positions
    pos[b]..pos[b]+C-1; k_pool / v_pool (P+1, ps, Hkv, D) are the SHARED
    physical page pools (page P is the trash page — runtime/paging.py);
    page_table (B, n) int32 maps logical page j of slot b to a physical
    page, -1 = unallocated (reads the trash page, fully masked).

    The grid is (B, C / bc, n) with pages minor-most (sequential on
    TPU): long chunks split into query blocks of bc positions
    (`_query_block`), each streaming the slot's pages again.  The page
    table is scalar-prefetched so each K/V BlockSpec fetch DMAs the one
    physical page it needs — no contiguous (B, n*ps) materialization
    ever exists.  Oracle: kernels/ref.paged_attention_ref."""
    b, c, hq, d = q.shape
    pn1, ps, hkv, _ = k_pool.shape
    n = page_table.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    sm_scale = float(sm_scale if sm_scale is not None else d ** -0.5)
    trash = pn1 - 1
    pt = jnp.where(page_table < 0, trash, page_table).astype(jnp.int32)
    bc = _query_block(c, hq)

    kernel = functools.partial(_paged_flash_kernel, sm_scale=sm_scale,
                               n_pages=n, trash=trash)
    q_spec = pl.BlockSpec((1, bc, hq, d),
                          lambda b, qi, j, pt_ref, pos_ref: (b, qi, 0, 0))
    kv_spec = pl.BlockSpec((1, ps, hkv, d),
                           lambda b, qi, j, pt_ref, pos_ref:
                           (pt_ref[b * n + j], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, c // bc, n),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hq * bc, d), jnp.float32),   # acc
            pltpu.VMEM((hq * bc, 1), jnp.float32),   # running max
            pltpu.VMEM((hq * bc, 1), jnp.float32),   # running denom
        ])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, hq, d), q.dtype),
        interpret=interpret, name="paged_flash_attention",
    )(pt.reshape(-1), jnp.asarray(pos, jnp.int32), q, k_pool, v_pool)


def flash_attention_bhsd(q, k, v, *, sm_scale=None, causal=True,
                         block_q=128, block_k=128, interpret=False):
    """q (BH, Sq, D); k/v (BHkv, Sk, D), BH % BHkv == 0, heads-major
    packing so that q row b uses kv row b // group (see ops.py).
    Requires Sq % block_q == Sk % block_k == 0 (ops.py pads)."""
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    assert bh % bhkv == 0, (bh, bhkv)
    group = bh // bhkv
    sm_scale = float(sm_scale if sm_scale is not None else d ** -0.5)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    n_q = sq // block_q
    n_k = sk // block_k

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki, g=group: (b // g, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki, g=group: (b // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
