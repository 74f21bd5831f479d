"""Fused absmax quant/dequant Pallas kernels for low-bit collectives.

The quantized sync-point path (parallel/compression.quantized_psum)
brackets every low-bit all-reduce with a per-chunk absmax quantize and a
dequantize.  Done as separate XLA ops those are 3 HBM round trips per
hop; the kernels here fuse absmax -> scale -> round -> (de)quant into one
VMEM pass over `(block_rows, chunk)` tiles:

    quantize_absmax   fp32 (N,) -> (int8 codes (N,), fp32 scales (N/chunk,))
    dequantize_absmax inverse
    qdq_absmax        fused round trip (what the CPU-simulated collective
                      consumes: the quantization ERROR without the int8
                      storage detour)

The chunk axis (default 128) matches the TPU lane width, so one scale
per lane row.  int4 is levels=7 in int8 storage — nibble packing is a
wire-format concern handled by the byte accounting in compression.py,
not a kernel concern.  On non-TPU backends pass interpret=True (tests);
the jnp oracles live in kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _tile(flat, chunk, block_rows):
    """(N,) -> ((R, chunk) zero-padded rows, block height).  The block is
    the whole array when it fits in `block_rows`, else `block_rows` high
    with R padded to a multiple of it — so every block is either the
    full array or a multiple of the int8 tile height (32 sublanes), the
    two shapes the TPU compiler accepts.  Zero rows quantize to zero."""
    assert block_rows % 32 == 0, block_rows
    rows = -(-flat.size // chunk)
    br = rows if rows <= block_rows else block_rows
    rows = -(-rows // br) * br
    return jnp.pad(flat, (0, rows * chunk - flat.size)).reshape(rows, chunk), br


def _scale_col(scales, rows):
    """(ceil(N/chunk),) scales -> the kernels' (R, 1) column."""
    s = scales.astype(jnp.float32).reshape(-1)
    return jnp.pad(s, (0, rows - s.size)).reshape(rows, 1)


def _scales(x, levels):
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / levels
    return jnp.maximum(s, 1e-12)


def _qdq_kernel(x_ref, y_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)
    s = _scales(x, levels)
    q = jnp.clip(jnp.round(x / s), -levels, levels)
    y_ref[...] = (q * s).astype(y_ref.dtype)


def _quant_kernel(x_ref, q_ref, s_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)
    s = _scales(x, levels)
    q_ref[...] = jnp.clip(jnp.round(x / s), -levels, levels).astype(jnp.int8)
    s_ref[...] = s


def _dequant_kernel(q_ref, s_ref, y_ref):
    y_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _dequant_accum_kernel(q_ref, s_ref, a_ref, y_ref):
    y_ref[...] = a_ref[...] + q_ref[...].astype(jnp.float32) * s_ref[...]


def _rows_spec(br, width):
    return pl.BlockSpec((br, width), lambda i: (i, 0))


@functools.partial(jax.jit, static_argnames=("chunk", "levels", "block_rows",
                                             "interpret"))
def qdq_absmax(x, *, chunk: int = 128, levels: int = 127,
               block_rows: int = 256, interpret: bool = False):
    """x (N,) -> quantize-dequantize round trip (fp32), per-chunk absmax."""
    flat = x.astype(jnp.float32).reshape(-1)
    x2d, br = _tile(flat, chunk, block_rows)
    y = pl.pallas_call(
        functools.partial(_qdq_kernel, levels=levels),
        grid=(x2d.shape[0] // br,),
        in_specs=[_rows_spec(br, chunk)],
        out_specs=_rows_spec(br, chunk),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.float32),
        interpret=interpret,
        name="qdq_absmax",
    )(x2d)
    return y.reshape(-1)[:flat.size]


@functools.partial(jax.jit, static_argnames=("chunk", "levels", "block_rows",
                                             "interpret"))
def quantize_absmax(x, *, chunk: int = 128, levels: int = 127,
                    block_rows: int = 256, interpret: bool = False):
    """x (N,) -> (codes int8 (N,), scales fp32 (ceil(N/chunk),))."""
    flat = x.astype(jnp.float32).reshape(-1)
    x2d, br = _tile(flat, chunk, block_rows)
    rows = x2d.shape[0]
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, levels=levels),
        grid=(rows // br,),
        in_specs=[_rows_spec(br, chunk)],
        out_specs=[_rows_spec(br, chunk), _rows_spec(br, 1)],
        out_shape=[jax.ShapeDtypeStruct((rows, chunk), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=interpret,
        name="quantize_absmax",
    )(x2d)
    return q.reshape(-1)[:flat.size], s[:-(-flat.size // chunk), 0]


@functools.partial(jax.jit, static_argnames=("n", "chunk", "block_rows",
                                             "interpret"))
def dequantize_absmax(q, scales, *, n: int, chunk: int = 128,
                      block_rows: int = 256, interpret: bool = False):
    """(codes int8 (N,), scales (ceil(N/chunk),)) -> fp32 (n,)."""
    q2d, br = _tile(q.astype(jnp.int8).reshape(-1), chunk, block_rows)
    rows = q2d.shape[0]
    y = pl.pallas_call(
        _dequant_kernel,
        grid=(rows // br,),
        in_specs=[_rows_spec(br, chunk), _rows_spec(br, 1)],
        out_specs=_rows_spec(br, chunk),
        out_shape=jax.ShapeDtypeStruct((rows, chunk), jnp.float32),
        interpret=interpret,
        name="dequantize_absmax",
    )(q2d, _scale_col(scales, rows))
    return y.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("chunk", "block_rows",
                                             "interpret"))
def dequant_accum_absmax(q, scales, acc, *, chunk: int = 128,
                         block_rows: int = 256, interpret: bool = False):
    """acc (N,) fp32 + dequant(q, scales) fused in one VMEM pass — the
    receive-side step of the quantized ring reduce-scatter
    (compression.ring_quantized_psum): each arriving chunk of int codes
    is widened, rescaled, and folded into the local partial without a
    separate dequantized intermediate hitting HBM."""
    flat = acc.astype(jnp.float32).reshape(-1)
    q2d, br = _tile(q.astype(jnp.int8).reshape(-1), chunk, block_rows)
    acc2d, _ = _tile(flat, chunk, block_rows)
    rows = q2d.shape[0]
    y = pl.pallas_call(
        _dequant_accum_kernel,
        grid=(rows // br,),
        in_specs=[_rows_spec(br, chunk), _rows_spec(br, 1),
                  _rows_spec(br, chunk)],
        out_specs=_rows_spec(br, chunk),
        out_shape=jax.ShapeDtypeStruct((rows, chunk), jnp.float32),
        interpret=interpret,
        name="dequant_accum_absmax",
    )(q2d, _scale_col(scales, rows), acc2d)
    return y.reshape(-1)[:flat.size]
