"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import (flash_attention_ref,
                               fused_residual_rmsnorm_ref)
from repro.models.ssm import ssd_chunked, ssd_reference


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,d,hq,hkv,bq,bk", [
    (128, 64, 2, 2, 128, 128),      # MHA, single block
    (256, 64, 4, 2, 128, 128),      # GQA group 2
    (384, 128, 6, 1, 128, 128),     # MQA, 3 q blocks, d=128
    (256, 32, 4, 4, 64, 128),       # small head dim, asym blocks
    (200, 64, 2, 2, 128, 128),      # ragged S (pads to 256)
])
def test_flash_attention_sweep(s, d, hq, hkv, bq, bk, dtype):
    rng = np.random.default_rng(s + d + hq)
    b = 2
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk,
                              interpret=True)
    qp = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kp = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vp = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    ref = flash_attention_ref(qp, kp, vp).reshape(b, hq, s, d) \
        .transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_matches_model_attention():
    """The kernel must agree with the model's XLA attention path."""
    from repro.models.attention import attention_any
    rng = np.random.default_rng(9)
    b, s, hq, hkv, d = 1, 256, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    scale = d ** -0.5
    xla = attention_any(q * scale / scale, k, v, pos, pos, q_chunk=128)
    pal = ops.flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(pal), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_fuzz(dtype):
    """Fused paged kernel vs the jnp oracle over random geometries:
    ragged per-slot lengths, -1 (unallocated) table entries, pages
    shared between rows, GQA/MQA/MHA head layouts, decode (C=1) and
    chunked (C>1) queries.  Tolerances mirror the flash sweep: the
    kernel accumulates in fp32, so bf16 error is input-rounding bound
    (2e-2) and fp32 is reduction-order bound (2e-5)."""
    from repro.kernels.ref import paged_attention_ref

    rng = np.random.default_rng(42)
    for trial in range(10):
        b = int(rng.integers(1, 4))
        c = int(rng.choice([1, 1, 4, 8]))
        hq, hkv = [(4, 4), (4, 2), (8, 1)][trial % 3]
        d = int(rng.choice([32, 64]))
        ps = int(rng.choice([8, 16]))
        width = int(rng.integers(2, 6))           # table width (pages)
        phys = int(rng.integers(width, 2 * width * b + 1))
        table = np.full((b, width), -1, np.int32)
        pos = np.zeros(b, np.int32)
        for r in range(b):
            # enough owned pages that the query chunk fits at `pos`
            own = int(rng.integers(max(1, (c + ps - 1) // ps), width + 1))
            # rows may alias the same physical page (read-only sharing)
            table[r, :own] = rng.integers(0, phys, own)
            pos[r] = int(rng.integers(0, own * ps - c + 1))
        q = jnp.asarray(rng.standard_normal((b, c, hq, d)), dtype)
        kp = jnp.asarray(rng.standard_normal((phys + 1, ps, hkv, d)), dtype)
        vp = jnp.asarray(rng.standard_normal((phys + 1, ps, hkv, d)), dtype)
        out = ops.paged_attention(q, kp, vp, jnp.asarray(table),
                                  jnp.asarray(pos), interpret=True)
        ref = paged_attention_ref(q, kp, vp, jnp.asarray(table),
                                  jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   err_msg=str((trial, b, c, hq, hkv, d,
                                                ps, width)),
                                   **_tol(dtype))


def test_paged_attention_query_blocks():
    """A chunk whose heads x positions exceed the kernel's row budget is
    split into query blocks (grid axis 1); every block must still see
    its own absolute positions in the causal mask."""
    from repro.kernels.flash_attention import PAGED_Q_ROWS, _query_block
    from repro.kernels.ref import paged_attention_ref

    rng = np.random.default_rng(3)
    b, c, hq, hkv, d, ps, width = 2, 128, 8, 2, 32, 16, 10
    assert _query_block(c, hq) < c and hq * c > PAGED_Q_ROWS
    table = np.stack([rng.permutation(2 * width)[:width] for _ in range(b)])
    table = table.astype(np.int32)
    table[1, 9:] = -1
    pos = np.asarray([16, 5], np.int32)
    q = jnp.asarray(rng.standard_normal((b, c, hq, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((2 * width + 1, ps, hkv, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((2 * width + 1, ps, hkv, d)),
                     jnp.float32)
    out = ops.paged_attention(q, kp, vp, jnp.asarray(table),
                              jnp.asarray(pos), interpret=True)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(table),
                              jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(jnp.float32))


@pytest.mark.parametrize("n_layers,layer", [(1, 0), (3, 1), (3, 2)])
def test_paged_scatter_gather_roundtrip(n_layers, layer):
    """scatter_tokens_pages places every token of the stacked pool's
    layer `layer` where gather_pages (the legacy dense view) finds it,
    -1 / out-of-range entries land in that layer's trash page, live
    pages of other slots are untouched, and so is every other layer."""
    rng = np.random.default_rng(7)
    ps, phys, width, b, c, tail = 4, 6, 3, 2, 3, (2, 5)
    pool = jnp.zeros((n_layers, phys + 1, ps) + tail, jnp.float32)
    table = np.asarray([[0, 3, -1], [5, -1, -1]], np.int32)
    pos = np.asarray([2, 1], np.int32)
    vals = jnp.asarray(rng.standard_normal((b, c) + tail), jnp.float32)
    out = np.asarray(ops.scatter_tokens_pages(
        pool, jnp.int32(layer), vals, jnp.asarray(table), jnp.asarray(pos)))
    assert np.delete(out, layer, axis=0).sum() == 0   # other layers
    dense = np.asarray(ops.gather_pages(jnp.asarray(out),
                                        jnp.asarray(table)))[layer]
    for r in range(b):
        for j in range(c):
            p = int(pos[r]) + j
            if table[r, p // ps] >= 0:
                np.testing.assert_array_equal(dense[r, p],
                                              np.asarray(vals)[r, j])
    # slot 0 wrote positions 2..4: page 0 offsets 2,3 + page 3 offset 0;
    # nothing past its own chunk is touched
    assert out[layer, 3, 1:].sum() == 0
    # a write through a -1 entry must hit ONLY the layer's trash page
    table2 = np.asarray([[-1, -1, -1], [5, -1, -1]], np.int32)
    out2 = np.asarray(ops.scatter_tokens_pages(
        pool, jnp.int32(layer), vals, jnp.asarray(table2), jnp.asarray(pos)))
    assert out2[layer, :5].sum() == 0             # pages 0..4 untouched
    assert np.delete(out2, layer, axis=0).sum() == 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,d,br", [(512, 96, 256), (100, 64, 256),
                                    (256, 960, 128)])
def test_fused_norm_sweep(t, d, br, dtype):
    rng = np.random.default_rng(t + d)
    x = jnp.asarray(rng.standard_normal((t, d)), dtype)
    r = jnp.asarray(rng.standard_normal((t, d)), dtype)
    w = jnp.asarray(rng.standard_normal(d), dtype)
    y, s = ops.fused_residual_rmsnorm(x, r, w, block_rows=br,
                                      interpret=True)
    yr, sr = fused_residual_rmsnorm_ref(x, r, w)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(s, np.float32),
                               np.asarray(sr, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,p,n,g,chunk", [
    (128, 2, 16, 32, 1, 32),
    (256, 4, 64, 16, 1, 64),
    (64, 3, 8, 8, 3, 16),          # per-head groups (G == H)
])
def test_ssd_kernel_sweep(s, h, p, n, g, chunk, dtype):
    rng = np.random.default_rng(s + h + n)
    b = 2
    x = jnp.asarray(rng.standard_normal((b, s, h, p)) * 0.5, dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), dtype)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, h), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.3, dtype)
    cm = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.3, dtype)
    dd = jnp.asarray(rng.standard_normal(h), jnp.float32)
    y = ops.ssd_scan(x, dt, a, bm, cm, dd, chunk=chunk, interpret=True)
    yr, _ = ssd_chunked(x, dt, a, bm, cm, dd, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               **(_tol(dtype) if dtype == jnp.bfloat16
                                  else dict(atol=2e-4, rtol=2e-3)))


def test_ssd_chunked_oracle_vs_sequential():
    """The oracle itself is validated against the O(S) recurrence."""
    rng = np.random.default_rng(0)
    b, s, h, p, n = 1, 32, 2, 4, 8
    x = jnp.asarray(rng.standard_normal((b, s, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (b, s, h)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.3, 2.0, h), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, s, 1, n)) * 0.4, jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, s, 1, n)) * 0.4, jnp.float32)
    dd = jnp.asarray(rng.standard_normal(h), jnp.float32)
    y1, _ = ssd_chunked(x, dt, a, bm, cm, dd, chunk=8)
    y2, _ = ssd_reference(x, dt, a, bm, cm, dd)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
