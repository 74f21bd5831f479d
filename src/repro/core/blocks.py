"""Decoder-block math for TP and SPD execution — THE paper's §4.1.

One definition, two engines: every collective is a named-axis op from
repro.parallel.collectives, so the same code runs under
``vmap(axis_name="model")`` (simulated TP) and ``shard_map`` (real TP).

Block wiring (Fig 3):

  TP block                       SPD block (no bias)
  --------                       -------------------
  h  = norm1(x)                  h   = norm1(x)
  y  = psum(attn(h))   <- SYNC   y_i = attn(h)            <- sync DROPPED
  u  = x + y                     u_i = x + y_i             (divergent)
  z  = psum(mlp(n2(u))) <- SYNC  s   = psum(mlp(n2(u_i)) + y_i)  <- SYNC
  out= u + z                     out = x + s

  SPD with out-proj bias b (Fig 3b): y_i = P_i + b feeds the MLP input;
  only P_i rides the deferred residual; b is re-added once after the sync:
  out = x + b + s,  s = psum(Z_i + P_i).

Parameters are stored in canonical (unpadded) form; `pad_layer` produces
the TP-layout tensors whose split axes are given by `layer_specs`.
Replicated params consumed inside shard-DIVERGENT regions (SPD norm2,
qk-norm, router, biases on the SPD path) are wrapped in `shared_param` so
their gradients accumulate across shards (see collectives.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig
from repro.core.layer_kinds import LayerKind
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.common import act_fn, apply_rope, fold_path, layernorm, rmsnorm
from repro.parallel.collectives import (
    MODEL_AXIS, column_entry, shared_param, sync_output)
from repro.parallel.layout import (
    REPLICATED, make_gqa_layout, pad_heads, q_head_orig, kv_head_orig)


# ---------------------------------------------------------------------------
# Small helpers
# ---------------------------------------------------------------------------

def _norm(x, p, cfg, *, shared: bool, axis):
    w = shared_param(p["w"], axis) if shared else p["w"]
    if cfg.norm == "layernorm":
        b = shared_param(p["b"], axis) if shared else p["b"]
        return layernorm(x, w, b, cfg.norm_eps)
    return rmsnorm(x, w, cfg.norm_eps)


def _mm(h, w):
    """Matmul against a possibly weight-quantized leaf.

    int8 leaves are {"q": int8 (in, out), "s": (out,)}: per-output-column
    scales commute with the contraction, so y = (h @ q) * s — the HBM read
    is 1 byte/weight (the serve-path memory-roofline lever)."""
    if isinstance(w, dict) and "q" in w:
        return (h @ w["q"].astype(h.dtype)) * w["s"].astype(h.dtype)
    return h @ w


QUANT_LEAVES = {
    "attn": ("wq", "wk", "wv", "wo"),
    "mlp": ("wu", "wg", "wd"),
}


def quantize_leaf(w):
    """(in, out) fp -> {"q" int8, "s" (out,) bf16} per-column absmax."""
    w32 = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w32), axis=0), 1e-12) / 127.0
    q = jnp.clip(jnp.round(w32 / s[None]), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s.astype(jnp.bfloat16)}


def quantize_layer_weights(padded_layer: dict, cfg, kind) -> dict:
    """Post-padding weight-only int8 for the serve path."""
    if cfg.weight_dtype != "int8":
        return padded_layer
    out = dict(padded_layer)
    for grp, names in QUANT_LEAVES.items():
        if grp not in out:
            continue
        g = dict(out[grp])
        for nm in names:
            if nm in g:
                g[nm] = quantize_leaf(g[nm])
        out[grp] = g
    return out


def _qleaf_spec(axis):
    """Spec subtree for a quantized (in,out) leaf split on `axis`."""
    from repro.parallel.layout import REPLICATED as R
    return {"q": axis, "s": 0 if axis == 1 else R}


def headwise_rmsnorm(x, w, eps, dh: int):
    """RMSNorm at per-head granularity over a head-packed channel axis.

    TP-invariant (a shard-local norm over d_local would change semantics
    with the TP degree): x (..., H*dh) -> normalize each dh group."""
    shape = x.shape
    xs = x.reshape(*shape[:-1], shape[-1] // dh, dh)
    ws = w.reshape(shape[-1] // dh, dh)
    return rmsnorm(xs, ws, eps).reshape(shape)


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


# derived ssm head count (keeps ModelConfig slim)
def ssm_heads(cfg: ModelConfig) -> int:
    s = cfg.ssm
    if cfg.family == "hybrid":
        return cfg.n_heads  # parallel ssm heads mirror attention heads
    d_in = s.expand * cfg.d_model
    return d_in // s.head_dim


# ---------------------------------------------------------------------------
# Parameter initialization (canonical, unpadded) + TP-layout specs
# ---------------------------------------------------------------------------

def _norm_init(cfg, d):
    p = {"w": jnp.ones((d,), _dt(cfg))}
    if cfg.norm == "layernorm":
        p["b"] = jnp.zeros((d,), _dt(cfg))
    return p


def _norm_spec(cfg):
    p = {"w": REPLICATED}
    if cfg.norm == "layernorm":
        p["b"] = REPLICATED
    return p


def _dense(key, path, d_in, d_out, cfg, scale=None):
    k = fold_path(key, path)
    s = scale if scale is not None else (1.0 / np.sqrt(d_in))
    return (jax.random.normal(k, (d_in, d_out), jnp.float32) * s).astype(_dt(cfg))


def init_attn(key, cfg: ModelConfig) -> dict:
    d, dh = cfg.d_model, cfg.d_head
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    p = {
        "wq": _dense(key, "wq", d, hq * dh, cfg),
        "wk": _dense(key, "wk", d, hkv * dh, cfg),
        "wv": _dense(key, "wv", d, hkv * dh, cfg),
        "wo": _dense(key, "wo", hq * dh, d, cfg,
                     scale=1.0 / np.sqrt(hq * dh) / np.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), _dt(cfg))
        p["bk"] = jnp.zeros((hkv * dh,), _dt(cfg))
        p["bv"] = jnp.zeros((hkv * dh,), _dt(cfg))
    if cfg.o_bias:
        p["bo"] = jnp.zeros((d,), _dt(cfg))
    if cfg.qk_norm:
        p["qn"] = jnp.ones((dh,), _dt(cfg))
        p["kn"] = jnp.ones((dh,), _dt(cfg))
    return p


def attn_specs(cfg: ModelConfig) -> dict:
    if cfg.weight_dtype == "int8":
        p = {"wq": _qleaf_spec(1), "wk": _qleaf_spec(1),
             "wv": _qleaf_spec(1), "wo": _qleaf_spec(0)}
    else:
        p = {"wq": 1, "wk": 1, "wv": 1, "wo": 0}
    if cfg.qkv_bias:
        p.update({"bq": 0, "bk": 0, "bv": 0})
    if cfg.o_bias:
        p["bo"] = REPLICATED
    if cfg.qk_norm:
        p.update({"qn": REPLICATED, "kn": REPLICATED})
    return p


def init_mla(key, cfg: ModelConfig) -> dict:
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    qd = h * (m.qk_nope_head_dim + m.qk_rope_head_dim)
    return {
        "wq": _dense(key, "wq", d, qd, cfg),
        "wdkv": _dense(key, "wdkv", d, m.kv_lora_rank + m.qk_rope_head_dim, cfg),
        "lnorm": jnp.ones((m.kv_lora_rank,), _dt(cfg)),
        "wuk": _dense(key, "wuk", m.kv_lora_rank, h * m.qk_nope_head_dim, cfg),
        "wuv": _dense(key, "wuv", m.kv_lora_rank, h * m.v_head_dim, cfg),
        "wo": _dense(key, "wo", h * m.v_head_dim, d, cfg,
                     scale=1.0 / np.sqrt(h * m.v_head_dim) / np.sqrt(2 * cfg.n_layers)),
    }


def mla_specs(cfg: ModelConfig) -> dict:
    return {"wq": 1, "wdkv": REPLICATED, "lnorm": REPLICATED,
            "wuk": 1, "wuv": 1, "wo": 0}


def init_ssm(key, cfg: ModelConfig) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    h = ssm_heads(cfg)
    d_in = h * s.head_dim
    gn = s.n_groups * s.d_state
    k1 = fold_path(key, "ssm")
    dt_init = jnp.log(jnp.expm1(
        jnp.exp(jax.random.uniform(fold_path(k1, "dtb"), (h,), jnp.float32,
                                   np.log(1e-3), np.log(1e-1)))))
    p = {
        "wz": _dense(k1, "wz", d, d_in, cfg),
        "wx": _dense(k1, "wx", d, d_in, cfg),
        "wbc": _dense(k1, "wbc", d, 2 * gn, cfg),
        "wdt": _dense(k1, "wdt", d, h, cfg),
        "dtb": dt_init.astype(_dt(cfg)),
        "alog": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(_dt(cfg)),
        "dd": jnp.ones((h,), _dt(cfg)),
        "convx": (jax.random.normal(fold_path(k1, "convx"),
                                    (s.d_conv, d_in), jnp.float32)
                  / np.sqrt(s.d_conv)).astype(_dt(cfg)),
        "convbc": (jax.random.normal(fold_path(k1, "convbc"),
                                     (s.d_conv, 2 * gn), jnp.float32)
                   / np.sqrt(s.d_conv)).astype(_dt(cfg)),
        "gn": jnp.ones((d_in,), _dt(cfg)),
        "wo": _dense(k1, "wo", d_in, d, cfg,
                     scale=1.0 / np.sqrt(d_in) / np.sqrt(2 * cfg.n_layers)),
    }
    return p


def ssm_specs(cfg: ModelConfig) -> dict:
    return {"wz": 1, "wx": 1, "wbc": REPLICATED, "wdt": 1, "dtb": 0,
            "alog": 0, "dd": 0, "convx": 1, "convbc": REPLICATED,
            "gn": 0, "wo": 0}


def init_mlp(key, cfg: ModelConfig, d_ff: int) -> dict:
    d = cfg.d_model
    p = {"wu": _dense(key, "wu", d, d_ff, cfg),
         "wd": _dense(key, "wd", d_ff, d, cfg,
                      scale=1.0 / np.sqrt(d_ff) / np.sqrt(2 * cfg.n_layers))}
    if cfg.gated_mlp:
        p["wg"] = _dense(key, "wg", d, d_ff, cfg)
    if cfg.mlp_bias:
        p["bu"] = jnp.zeros((d_ff,), _dt(cfg))
        p["bd"] = jnp.zeros((d,), _dt(cfg))
        if cfg.gated_mlp:
            p["bg"] = jnp.zeros((d_ff,), _dt(cfg))
    return p


def mlp_specs(cfg: ModelConfig) -> dict:
    if cfg.weight_dtype == "int8":
        p = {"wu": _qleaf_spec(1), "wd": _qleaf_spec(0)}
        if cfg.gated_mlp:
            p["wg"] = _qleaf_spec(1)
    else:
        p = {"wu": 1, "wd": 0}
        if cfg.gated_mlp:
            p["wg"] = 1
    if cfg.mlp_bias:
        p.update({"bu": 0, "bd": REPLICATED})
        if cfg.gated_mlp:
            p["bg"] = 0
    return p


def init_moe(key, cfg: ModelConfig) -> dict:
    mo, d = cfg.moe, cfg.d_model
    ff = mo.d_ff_expert
    e = mo.n_routed
    k = fold_path(key, "moe")

    def experts(name, din, dout):
        ws = jax.random.normal(fold_path(k, name), (e, din, dout), jnp.float32)
        return (ws / np.sqrt(din)).astype(_dt(cfg))

    p = {
        "router": _dense(k, "router", d, e, cfg, scale=0.02),
        "wu": experts("wu", d, ff),
        "wd": (jax.random.normal(fold_path(k, "wd"), (e, ff, d), jnp.float32)
               / np.sqrt(ff) / np.sqrt(2 * cfg.n_layers)).astype(_dt(cfg)),
    }
    if cfg.gated_mlp:
        p["wg"] = experts("wg", d, ff)
    if mo.n_shared:
        sff = mo.n_shared * ff
        p["su"] = _dense(k, "su", d, sff, cfg)
        p["sd"] = _dense(k, "sd", sff, d, cfg,
                         scale=1.0 / np.sqrt(sff) / np.sqrt(2 * cfg.n_layers))
        if cfg.gated_mlp:
            p["sg"] = _dense(k, "sg", d, sff, cfg)
    return p


def moe_specs(cfg: ModelConfig) -> dict:
    p = {"router": REPLICATED, "wu": 0, "wd": 0}
    if cfg.gated_mlp:
        p["wg"] = 0
    if cfg.moe.n_shared:
        p.update({"su": 1, "sd": 0})
        if cfg.gated_mlp:
            p["sg"] = 1
    return p


def init_layer(key, cfg: ModelConfig, kind: LayerKind) -> dict:
    p = {"ln1": _norm_init(cfg, cfg.d_model)}
    if kind.mixer == "gqa":
        p["attn"] = init_attn(key, cfg)
    elif kind.mixer == "mla":
        p["attn"] = init_mla(key, cfg)
    elif kind.mixer == "ssm":
        p["ssm"] = init_ssm(key, cfg)
    elif kind.mixer == "hybrid":
        p["attn"] = init_attn(key, cfg)
        p["ssm"] = init_ssm(key, cfg)
        hd = cfg.n_heads * cfg.d_head
        p["na"] = jnp.ones((hd,), _dt(cfg))
        p["ns"] = jnp.ones((hd,), _dt(cfg))
    if kind.ffn != "none":
        p["ln2"] = _norm_init(cfg, cfg.d_model)
        if kind.ffn == "moe":
            p["moe"] = init_moe(key, cfg)
        else:
            p["mlp"] = init_mlp(key, cfg, kind.d_ff or cfg.d_ff)
    return p


def layer_specs(cfg: ModelConfig, kind: LayerKind) -> dict:
    p = {"ln1": _norm_spec(cfg)}
    if kind.mixer == "gqa":
        p["attn"] = attn_specs(cfg)
    elif kind.mixer == "mla":
        p["attn"] = mla_specs(cfg)
    elif kind.mixer == "ssm":
        p["ssm"] = ssm_specs(cfg)
    elif kind.mixer == "hybrid":
        p["attn"] = attn_specs(cfg)
        p["ssm"] = ssm_specs(cfg)
        p["na"] = 0
        p["ns"] = 0
    if kind.ffn != "none":
        p["ln2"] = _norm_spec(cfg)
        p["moe" if kind.ffn == "moe" else "mlp"] = (
            moe_specs(cfg) if kind.ffn == "moe" else mlp_specs(cfg))
    return p


# ---------------------------------------------------------------------------
# Canonical -> TP layout (head/vocab/expert padding)
# ---------------------------------------------------------------------------

def pad_layer(p: dict, cfg: ModelConfig, kind: LayerKind, tp: int) -> dict:
    """Pad canonical layer params so every split axis divides by tp."""
    out = jax.tree.map(lambda x: x, p)  # shallow-ish copy
    dh = cfg.d_head
    if kind.mixer in ("gqa", "hybrid"):
        lay = make_gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
        qmap, kvmap = q_head_orig(lay), kv_head_orig(lay)
        a = dict(p["attn"])
        a["wq"] = pad_heads(a["wq"], 1, qmap, dh, cfg.n_heads)
        a["wo"] = pad_heads(a["wo"], 0, qmap, dh, cfg.n_heads)
        for nm in ("wk", "wv"):
            a[nm] = pad_heads(a[nm], 1, kvmap, dh, cfg.n_kv_heads)
        if cfg.qkv_bias:
            a["bq"] = pad_heads(a["bq"], 0, qmap, dh, cfg.n_heads)
            a["bk"] = pad_heads(a["bk"], 0, kvmap, dh, cfg.n_kv_heads)
            a["bv"] = pad_heads(a["bv"], 0, kvmap, dh, cfg.n_kv_heads)
        out["attn"] = a
    if kind.mixer in ("ssm", "hybrid"):
        s = cfg.ssm
        h = ssm_heads(cfg)
        if kind.mixer == "hybrid":
            lay = make_gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)
            hmap = q_head_orig(lay)
        else:
            hp = -(-h // tp) * tp
            hmap = np.concatenate([np.arange(h), -np.ones(hp - h, np.int64)])
        ss = dict(p["ssm"])
        hd = s.head_dim
        for nm in ("wz", "wx"):
            ss[nm] = pad_heads(ss[nm], 1, hmap, hd, h)
        ss["wdt"] = pad_heads(ss["wdt"], 1, hmap, 1, h)
        for nm in ("dtb", "alog", "dd"):
            ss[nm] = pad_heads(ss[nm], 0, hmap, 1, h)
        ss["convx"] = pad_heads(ss["convx"], 1, hmap, hd, h)
        ss["gn"] = pad_heads(ss["gn"], 0, hmap, hd, h)
        ss["wo"] = pad_heads(ss["wo"], 0, hmap, hd, h)
        out["ssm"] = ss
        if kind.mixer == "hybrid":
            out["na"] = pad_heads(p["na"], 0, hmap, hd, h)
            out["ns"] = pad_heads(p["ns"], 0, hmap, hd, h)
    if kind.ffn == "mlp":
        m = dict(p["mlp"])
        ff = m["wu"].shape[1]
        ffp = -(-ff // tp) * tp
        if ffp != ff:
            padm = np.concatenate([np.arange(ff), -np.ones(ffp - ff, np.int64)])
            for nm in ("wu", "wg", "bu", "bg"):
                if nm in m:
                    m[nm] = pad_heads(m[nm], 1 if nm[0] == "w" else 0, padm, 1, ff)
            m["wd"] = pad_heads(m["wd"], 0, padm, 1, ff)
        out["mlp"] = m
    if kind.ffn == "moe":
        mo = cfg.moe
        m = dict(p["moe"])
        e = mo.n_routed
        ep = -(-e // tp) * tp
        if ep != e:
            emap = np.concatenate([np.arange(e), -np.ones(ep - e, np.int64)])
            for nm in ("wu", "wg", "wd"):
                if nm in m:
                    m[nm] = pad_heads(m[nm], 0, emap, 1, e)
            m["router"] = pad_heads(m["router"], 1, emap, 1, e)
        out["moe"] = m
    return out


# ---------------------------------------------------------------------------
# Mixers (shard-local partial output, NO sync applied here)
# ---------------------------------------------------------------------------

def _qkv(cfg, a, h, lay, axis):
    """h (B,S,d) -> q (B,S,HqL,dh), k,v (B,S,HkvL,dh) shard-local."""
    dh = cfg.d_head
    q = _mm(h, a["wq"])
    k = _mm(h, a["wk"])
    v = _mm(h, a["wv"])
    if cfg.qkv_bias:
        q = q + a["bq"]
        k = k + a["bk"]
        v = v + a["bv"]
    b, s = h.shape[:2]
    q = q.reshape(b, s, lay.q_local if lay else cfg.n_heads, dh)
    nkv = lay.kv_local if lay else cfg.n_kv_heads
    k = k.reshape(b, s, nkv, dh)
    v = v.reshape(b, s, nkv, dh)
    if cfg.qk_norm:
        qn = shared_param(a["qn"], axis)
        kn = shared_param(a["kn"], axis)
        q = rmsnorm(q, qn, cfg.norm_eps)
        k = rmsnorm(k, kn, cfg.norm_eps)
    return q, k, v


def _pack_kv(cfg, kc, vc):
    if cfg.kv_dtype != "int8":
        return {"k": kc, "v": vc}
    kq, ks = A.kv_quantize(kc)
    vq, vs = A.kv_quantize(vc)
    return {"k": kq, "k_s": ks, "v": vq, "v_s": vs}


def _unpack_kv(cfg, cache, dtype):
    if cfg.kv_dtype != "int8":
        return cache["k"], cache["v"]
    return (A.kv_dequantize(cache["k"], cache["k_s"], dtype),
            A.kv_dequantize(cache["v"], cache["v_s"], dtype))


def _update_kv(cfg, cache, k_new, v_new, pos, window):
    """Write one token (decode path), quantizing when kv_dtype=int8."""
    if cfg.kv_dtype != "int8":
        kc, vc = A.cache_update(cache["k"], cache["v"], k_new, v_new, pos,
                                window=window)
        return {"k": kc, "v": vc}
    slot = pos % window if window > 0 else pos
    bi = jnp.arange(cache["k"].shape[0])
    kq, ks = A.kv_quantize(k_new[:, 0])
    vq, vs = A.kv_quantize(v_new[:, 0])
    return {"k": cache["k"].at[bi, slot].set(kq),
            "k_s": cache["k_s"].at[bi, slot].set(ks),
            "v": cache["v"].at[bi, slot].set(vq),
            "v_s": cache["v_s"].at[bi, slot].set(vs)}


def gqa_mixer_seq(cfg, kind, a, h, pos, lay, axis, *, want_cache=False,
                  q_chunk=1024):
    """Sequence (train/prefill) attention; returns (partial (B,S,d_local->d), cache)."""
    q, k, v = _qkv(cfg, a, h, lay, axis)
    q = apply_rope(q, pos, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, pos, cfg.rope_theta, cfg.rope_fraction)
    if cfg.attn_backend == "pallas" and kind.window == 0:
        # Pallas flash kernel (TPU target; interpret=True executes the
        # kernel body on CPU).  Full-causal only; windowed layers and
        # decode fall back to the XLA path.
        import jax as _jax
        from repro.kernels import ops as KOPS
        interp = _jax.default_backend() != "tpu"
        o = KOPS.flash_attention(q, k, v, interpret=interp)
    else:
        o = A.attention_any(q, k, v, pos, pos, window=kind.window,
                            q_chunk=q_chunk)
    b, s = h.shape[:2]
    o = o.reshape(b, s, -1)
    part = _mm(o, a["wo"])
    cache = None
    if want_cache:
        w = kind.window
        if w and s >= w:
            sl = (np.arange(s - w, s) % w)
            kc = jnp.zeros_like(k[:, :w]).at[:, sl].set(k[:, -w:])
            vc = jnp.zeros_like(v[:, :w]).at[:, sl].set(v[:, -w:])
        else:
            kc, vc = k, v
        cache = _pack_kv(cfg, kc, vc)
    return part, cache


def gqa_mixer_dec(cfg, kind, a, h, pos, cache, lay, axis):
    """Decode attention: h (B,1,d); cache {"k","v"[,"k_s","v_s"]}."""
    q, k, v = _qkv(cfg, a, h, lay, axis)
    q = apply_rope(q, pos[:, None], cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, pos[:, None], cfg.rope_theta, cfg.rope_fraction)
    cache = _update_kv(cfg, cache, k, v, pos, kind.window)
    kc, vc = _unpack_kv(cfg, cache, h.dtype)
    o = A.decode_attend(q, kc, vc, pos, window=kind.window)
    b = h.shape[0]
    part = _mm(o.reshape(b, 1, -1), a["wo"])
    return part, cache


def _mla_qkr(cfg, a, h, pos, axis):
    m = cfg.mla
    b, s = h.shape[:2]
    hq = cfg.n_heads
    tp_now = a["wq"].shape[1] // ((m.qk_nope_head_dim + m.qk_rope_head_dim))
    hl = tp_now  # local heads
    q = (h @ a["wq"]).reshape(b, s, hl, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    # shared latent path (replicated compute; params need grad-accumulation)
    wdkv = shared_param(a["wdkv"], axis)
    ckr = h @ wdkv
    c, kr = ckr[..., : m.kv_lora_rank], ckr[..., m.kv_lora_rank:]
    c = rmsnorm(c, shared_param(a["lnorm"], axis), cfg.norm_eps)
    kr = apply_rope(kr[:, :, None, :], pos, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, c, kr, hl


def mla_mixer_seq(cfg, kind, a, h, pos, axis, *, want_cache=False,
                  q_chunk=1024):
    m = cfg.mla
    b, s = h.shape[:2]
    q_nope, q_rope, c, kr, hl = _mla_qkr(cfg, a, h, pos, axis)
    k_nope = (c @ a["wuk"]).reshape(b, s, hl, m.qk_nope_head_dim)
    v = (c @ a["wuv"]).reshape(b, s, hl, m.v_head_dim)
    # pack rope part into head dim; pad v to same width for shared attend
    q_full = jnp.concatenate([q_nope, q_rope], -1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, :, None], q_rope.shape[:2] + (hl, m.qk_rope_head_dim))], -1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    o = A.attention_any(q_full, k_full, v, pos, pos, window=0,
                        q_chunk=q_chunk, scale=scale)
    part = o.reshape(b, s, -1) @ a["wo"]
    cache = {"c": c, "kr": kr} if want_cache else None
    return part, cache


def mla_mixer_dec(cfg, kind, a, h, pos, cache, axis):
    """Absorbed-form MLA decode: cache holds the latent (replicated over TP)."""
    m = cfg.mla
    b = h.shape[0]
    q_nope, q_rope, c_new, kr_new, hl = _mla_qkr(cfg, a, h, pos[:, None], axis)
    bi = jnp.arange(b)
    c = cache["c"].at[bi, pos].set(c_new[:, 0])
    kr = cache["kr"].at[bi, pos].set(kr_new[:, 0])
    # absorb: q_lat[h] = q_nope[h] @ wuk[:,h].T  -> (B,1,hl,lora)
    wuk = a["wuk"].reshape(m.kv_lora_rank, hl, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bshn,lhn->bshl", q_nope.astype(jnp.float32),
                       wuk.astype(jnp.float32))
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s_lat = jnp.einsum("bshl,btl->bhst", q_lat, c.astype(jnp.float32))
    s_rope = jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                        kr.astype(jnp.float32))
    scores = (s_lat + s_rope) * scale
    valid = (jnp.arange(c.shape[1])[None] <= pos[:, None])[:, None, None]
    scores = jnp.where(valid, scores, A.NEG_INF)
    pattn = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhst,btl->bshl", pattn, c.astype(jnp.float32))
    wuv = a["wuv"].reshape(m.kv_lora_rank, hl, m.v_head_dim)
    o = jnp.einsum("bshl,lhv->bshv", o_lat, wuv.astype(jnp.float32))
    part = o.reshape(b, 1, -1).astype(h.dtype) @ a["wo"]
    return part, {"c": c, "kr": kr}


def _ssm_in(cfg, ss, h, axis, conv_state=None):
    """Shared ssm input path; h (B,S,d). Returns per-head tensors."""
    s = cfg.ssm
    z = h @ ss["wz"]
    x = h @ ss["wx"]
    wbc = shared_param(ss["wbc"], axis)
    bc = h @ wbc
    dt = jax.nn.softplus((h @ ss["wdt"]).astype(jnp.float32)
                         + ss["dtb"].astype(jnp.float32))
    cs_x = cs_bc = None
    if conv_state is not None:
        x, cs_x = SSM.causal_conv(x, ss["convx"], conv_state["x"])
        bc, cs_bc = SSM.causal_conv(bc, shared_param(ss["convbc"], axis),
                                    conv_state["bc"])
    else:
        x, cs_x = SSM.causal_conv(x, ss["convx"])
        bc, cs_bc = SSM.causal_conv(bc, shared_param(ss["convbc"], axis))
    x = jax.nn.silu(x)
    bc = jax.nn.silu(bc)
    gn_ = s.n_groups * s.d_state
    bm = bc[..., :gn_].reshape(*bc.shape[:2], s.n_groups, s.d_state)
    cm = bc[..., gn_:].reshape(*bc.shape[:2], s.n_groups, s.d_state)
    b_, s_ = h.shape[:2]
    hloc = x.shape[-1] // s.head_dim
    x = x.reshape(b_, s_, hloc, s.head_dim)
    return z, x, bm, cm, dt, {"x": cs_x, "bc": cs_bc}


def _ssm_out(cfg, ss, y, z, axis, shared_gn: bool):
    """Gated per-head norm + out projection. y,z (B,S,d_in_local).
    `gn` is channel-SHARDED, so no grad-accumulation wrapper."""
    y = headwise_rmsnorm(y * jax.nn.silu(z), ss["gn"], cfg.norm_eps,
                         cfg.ssm.head_dim)
    return y @ ss["wo"]


def ssm_mixer_seq(cfg, ss, h, axis, *, want_cache=False):
    s = cfg.ssm
    z, x, bm, cm, dt, conv_cache = _ssm_in(cfg, ss, h, axis)
    A_ = -jnp.exp(ss["alog"].astype(jnp.float32))
    chunk = min(s.chunk_size, x.shape[1])
    if x.shape[1] % chunk:
        chunk = x.shape[1]
    y, state = SSM.ssd_chunked(x, dt, A_, bm, cm, ss["dd"], chunk=chunk)
    b_, s_ = h.shape[:2]
    y = y.reshape(b_, s_, -1)
    part = _ssm_out(cfg, ss, y, z, axis, True)
    cache = {"state": state.astype(_dt(cfg)), "conv": conv_cache} if want_cache else None
    return part, cache


def ssm_mixer_dec(cfg, ss, h, cache, axis):
    z, x, bm, cm, dt, conv_cache = _ssm_in(cfg, ss, h, axis,
                                           conv_state=cache["conv"])
    A_ = -jnp.exp(ss["alog"].astype(jnp.float32))
    y, state = SSM.ssd_decode_step(x, dt, A_, bm, cm, ss["dd"],
                                   cache["state"].astype(jnp.float32))
    b_ = h.shape[0]
    y = y.reshape(b_, 1, -1)
    part = _ssm_out(cfg, ss, y, z, axis, True)
    return part, {"state": state.astype(_dt(cfg)), "conv": conv_cache}


def hybrid_mixer_seq(cfg, kind, p, h, pos, lay, axis, *, want_cache=False,
                     q_chunk=1024):
    """Hymba-style: attention + SSM heads in parallel, mean-fused."""
    a = p["attn"]
    q, k, v = _qkv(cfg, a, h, lay, axis)
    q = apply_rope(q, pos, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, pos, cfg.rope_theta, cfg.rope_fraction)
    o_attn = A.attention_any(q, k, v, pos, pos, window=kind.window,
                             q_chunk=q_chunk)
    b, s = h.shape[:2]
    o_attn = o_attn.reshape(b, s, -1)
    ss = p["ssm"]
    z, x, bm, cm, dt, conv_cache = _ssm_in(cfg, ss, h, axis)
    A_ = -jnp.exp(ss["alog"].astype(jnp.float32))
    chunk = min(cfg.ssm.chunk_size, x.shape[1])
    if x.shape[1] % chunk:
        chunk = x.shape[1]
    y_ssm, state = SSM.ssd_chunked(x, dt, A_, bm, cm, ss["dd"], chunk=chunk)
    y_ssm = y_ssm.reshape(b, s, -1)
    y_ssm = y_ssm * jax.nn.silu(z)
    fused = 0.5 * (headwise_rmsnorm(o_attn, p["na"], cfg.norm_eps, cfg.d_head)
                   + headwise_rmsnorm(y_ssm, p["ns"], cfg.norm_eps, cfg.d_head))
    part = fused @ a["wo"]
    cache = None
    if want_cache:
        w = kind.window
        if w and s >= w:
            sl = (np.arange(s - w, s) % w)
            kc = jnp.zeros_like(k[:, :w]).at[:, sl].set(k[:, -w:])
            vc = jnp.zeros_like(v[:, :w]).at[:, sl].set(v[:, -w:])
        else:
            kc, vc = k, v
        cache = dict(_pack_kv(cfg, kc, vc),
                     state=state.astype(_dt(cfg)), conv=conv_cache)
    return part, cache


def hybrid_mixer_dec(cfg, kind, p, h, pos, cache, lay, axis):
    a = p["attn"]
    q, k, v = _qkv(cfg, a, h, lay, axis)
    q = apply_rope(q, pos[:, None], cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, pos[:, None], cfg.rope_theta, cfg.rope_fraction)
    kv_cache = {kk: cache[kk] for kk in ("k", "v", "k_s", "v_s")
                if kk in cache}
    kv_cache = _update_kv(cfg, kv_cache, k, v, pos, kind.window)
    kc, vc = _unpack_kv(cfg, kv_cache, h.dtype)
    o_attn = A.decode_attend(q, kc, vc, pos, window=kind.window)
    b = h.shape[0]
    o_attn = o_attn.reshape(b, 1, -1)
    ss = p["ssm"]
    z, x, bm, cm, dt, conv_cache = _ssm_in(cfg, ss, h, axis,
                                           conv_state=cache["conv"])
    A_ = -jnp.exp(ss["alog"].astype(jnp.float32))
    y_ssm, state = SSM.ssd_decode_step(x, dt, A_, bm, cm, ss["dd"],
                                       cache["state"].astype(jnp.float32))
    y_ssm = y_ssm.reshape(b, 1, -1) * jax.nn.silu(z)
    fused = 0.5 * (headwise_rmsnorm(o_attn, p["na"], cfg.norm_eps, cfg.d_head)
                   + headwise_rmsnorm(y_ssm, p["ns"], cfg.norm_eps, cfg.d_head))
    part = fused @ a["wo"]
    return part, dict(kv_cache, state=state.astype(_dt(cfg)),
                      conv=conv_cache)


# ---------------------------------------------------------------------------
# FFN partials (shard-local, NO sync applied here)
# ---------------------------------------------------------------------------

def mlp_partial(cfg, m, h, axis, *, divergent: bool):
    act = act_fn(cfg.act)

    def maybe_shared(x):
        return shared_param(x, axis) if divergent else x

    up = _mm(h, m["wu"])
    if cfg.mlp_bias:
        up = up + m["bu"]
    if cfg.gated_mlp:
        g = _mm(h, m["wg"])
        if cfg.mlp_bias:
            g = g + m["bg"]
        hid = act(g) * up
    else:
        hid = act(up)
    z = _mm(hid, m["wd"])
    return z  # wd bias (bd) handled at the sync point by the caller


def moe_partial(cfg, mo_p, h, axis, tp: int, shard_idx, h_aux=None):
    """h (B,S,d) -> partial combine (B,S,d) + aux loss.

    GRADIENT SUBTLETY: the combine path's cotangents are shard-DISTINCT
    (each shard sees only its local experts), so `h` arrives through
    column_entry (bwd psum) and the router through shared_param — correct.
    The AUX load-balance loss, however, is computed IDENTICALLY on every
    shard; routing its gradient through those same wrappers would count
    it tp times.  In TP mode the caller passes `h_aux` = the replicated
    pre-entry activation, and aux uses the RAW router — counted once.
    (SPD mode: the input is genuinely divergent, aux is per-shard by
    construction; h_aux is None and the wrapped path is correct.)"""
    mo = cfg.moe
    b, s, d = h.shape
    t = b * s
    hf = h.reshape(t, d)
    router = shared_param(mo_p["router"], axis)
    gates, idx, _ = MOE.route(hf, router, mo.top_k, mo.n_routed)
    if h_aux is not None:
        _, _, aux = MOE.route(h_aux.reshape(t, d), mo_p["router"],
                              mo.top_k, mo.n_routed)
    else:
        _, _, aux = MOE.route(hf, router, mo.top_k, mo.n_routed)
    e_l = mo_p["wu"].shape[0]
    e_lo = shard_idx * e_l
    cap = int(mo.capacity_factor * t * mo.top_k / max(mo.n_routed, 1))
    cap = max(cap, mo.top_k)
    slot_token, tok_slot = MOE.dispatch_local(idx, gates, e_lo, e_l, cap)
    part = MOE.moe_local(hf, gates, tok_slot, slot_token,
                         mo_p.get("wg"), mo_p["wu"], mo_p["wd"],
                         cfg.act, cfg.gated_mlp)
    part = part.reshape(b, s, d).astype(h.dtype)
    if mo.n_shared:
        act = act_fn(cfg.act)
        up = hf @ mo_p["su"]
        hid = act(hf @ mo_p["sg"]) * up if cfg.gated_mlp else act(up)
        part = part + (hid @ mo_p["sd"]).reshape(b, s, d)
    return part, aux


# ---------------------------------------------------------------------------
# Full blocks: TP vs SPD wiring
# ---------------------------------------------------------------------------

def _mixer_seq(cfg, kind, p, x, pos, lay, axis, want_cache, q_chunk):
    """norm1 -> column entry -> mixer partial.  Returns (partial, bias_o, cache)."""
    h = _norm(x, p["ln1"], cfg, shared=False, axis=axis)
    h = column_entry(h, axis)
    if kind.mixer == "gqa":
        part, cache = gqa_mixer_seq(cfg, kind, p["attn"], h, pos, lay, axis,
                                    want_cache=want_cache, q_chunk=q_chunk)
        bo = p["attn"].get("bo")
    elif kind.mixer == "mla":
        part, cache = mla_mixer_seq(cfg, kind, p["attn"], h, pos, axis,
                                    want_cache=want_cache, q_chunk=q_chunk)
        bo = None
    elif kind.mixer == "hybrid":
        part, cache = hybrid_mixer_seq(cfg, kind, p, h, pos, lay, axis,
                                       want_cache=want_cache, q_chunk=q_chunk)
        bo = p["attn"].get("bo")
    else:
        raise ValueError(kind.mixer)
    return part, bo, cache


def _ffn_partial(cfg, kind, p, u, axis, tp, shard_idx, *, divergent):
    """norm2 -> (column entry) -> ffn partial. Returns (z_partial, bd, aux)."""
    h2_raw = _norm(u, p["ln2"], cfg, shared=divergent, axis=axis)
    h2 = h2_raw if divergent else column_entry(h2_raw, axis)
    if kind.ffn == "moe":
        z, aux = moe_partial(cfg, p["moe"], h2, axis, tp, shard_idx,
                             h_aux=None if divergent else h2_raw)
        return z, None, aux
    z = mlp_partial(cfg, p["mlp"], h2, axis, divergent=divergent)
    bd = p["mlp"].get("bd")
    return z, bd, jnp.zeros((), jnp.float32)


def block_seq(cfg, kind, lay, p, x, pos, *, drop: bool, tp: int, shard_idx,
              axis=MODEL_AXIS, want_cache=False, q_chunk=1024, comm=None):
    """Sequence-mode decoder block (train / prefill).

    `comm` is the block's kept-sync level from its CommPolicy ("exact" |
    "quant8" | "quant4"; None defers to the sync_compression context) —
    it reaches every sync_output this block KEEPS, so a dropped block's
    surviving MLP combine can still run low-bit.

    Returns (out (B,S,d), aux_loss, cache).
    """
    if kind.mixer == "ssm":
        # single-sync block: SPD structurally inapplicable
        h = _norm(x, p["ln1"], cfg, shared=False, axis=axis)
        h = column_entry(h, axis)
        part, cache = ssm_mixer_seq(cfg, p["ssm"], h, axis,
                                    want_cache=want_cache)
        out = x + sync_output(part, axis, mode=comm)
        return out, jnp.zeros((), jnp.float32), cache

    part, bo, cache = _mixer_seq(cfg, kind, p, x, pos, lay, axis,
                                 want_cache, q_chunk)
    if not drop:
        y = sync_output(part, axis, mode=comm)
        if bo is not None:
            y = y + bo
        u = x + y
        z, bd, aux = _ffn_partial(cfg, kind, p, u, axis, tp, shard_idx,
                                  divergent=False)
        z = sync_output(z, axis, mode=comm)
        if bd is not None:
            z = z + bd
        out = u + z
    else:
        # ---- SPD wiring (Fig 3) ----
        y_i = part
        if bo is not None:
            y_i = y_i + shared_param(bo, axis)     # b on the divergent path
        # column_entry: the incoming replicated stream is consumed
        # DIVERGENTLY here; without the bwd psum, each copy's cotangent
        # would miss the other shards' u_i-path contributions (exact at
        # block level but wrong across block chains — caught by the
        # finite-difference test).
        u_i = column_entry(x, axis) + y_i
        z_i, bd, aux = _ffn_partial(cfg, kind, p, u_i, axis, tp, shard_idx,
                                    divergent=True)
        # deferred residual: P_i only
        s = sync_output(z_i + part, axis, mode=comm)
        out = x + s
        if bo is not None:
            out = out + bo                          # bias re-added once
        if bd is not None:
            out = out + bd
    return out, aux, cache


def _wire_post_mixer(cfg, kind, p, x, part, bo, *, drop: bool, tp: int,
                     shard_idx, axis, comm=None):
    """TP/SPD post-mixer wiring shared by the cached paths (decode and
    chunked-prefill extension) — block_seq's Fig 3 wiring minus the aux
    plumbing.  x is the block input, `part` the shard-local mixer partial."""
    if not drop:
        y = sync_output(part, axis, mode=comm)
        if bo is not None:
            y = y + bo
        u = x + y
        z, bd, _ = _ffn_partial(cfg, kind, p, u, axis, tp, shard_idx,
                                divergent=False)
        z = sync_output(z, axis, mode=comm)
        if bd is not None:
            z = z + bd
        return u + z
    y_i = part
    if bo is not None:
        y_i = y_i + shared_param(bo, axis)
    u_i = column_entry(x, axis) + y_i   # see block_seq note
    z_i, bd, _ = _ffn_partial(cfg, kind, p, u_i, axis, tp, shard_idx,
                              divergent=True)
    s = sync_output(z_i + part, axis, mode=comm)
    out = x + s
    if bo is not None:
        out = out + bo
    if bd is not None:
        out = out + bd
    return out


def block_dec(cfg, kind, lay, p, x, pos, cache, *, drop: bool, tp: int,
              shard_idx, axis=MODEL_AXIS, comm=None):
    """Decode-mode block: x (B,1,d), per-seq pos (B,). Returns (out, cache)."""
    if kind.mixer == "ssm":
        h = _norm(x, p["ln1"], cfg, shared=False, axis=axis)
        h = column_entry(h, axis)
        part, cache = ssm_mixer_dec(cfg, p["ssm"], h, cache, axis)
        return x + sync_output(part, axis, mode=comm), cache

    h = _norm(x, p["ln1"], cfg, shared=False, axis=axis)
    h = column_entry(h, axis)
    if kind.mixer == "gqa":
        part, cache = gqa_mixer_dec(cfg, kind, p["attn"], h, pos, cache, lay, axis)
        bo = p["attn"].get("bo")
    elif kind.mixer == "mla":
        part, cache = mla_mixer_dec(cfg, kind, p["attn"], h, pos, cache, axis)
        bo = None
    elif kind.mixer == "hybrid":
        part, cache = hybrid_mixer_dec(cfg, kind, p, h, pos, cache, lay, axis)
        bo = p["attn"].get("bo")
    else:
        raise ValueError(kind.mixer)

    out = _wire_post_mixer(cfg, kind, p, x, part, bo, drop=drop, tp=tp,
                           shard_idx=shard_idx, axis=axis, comm=comm)
    return out, cache


# ---------------------------------------------------------------------------
# Chunked prefill (cache-extension mode): a chunk of C tokens is run
# seq-mode against an existing decode cache, writing its K/V at absolute
# positions and attending over the whole buffer with position masking.
# GQA/full-causal layers only (model.supports_chunked_prefill gates
# callers); rolling-window and SSM/MLA layers fall back to full prefill.
# ---------------------------------------------------------------------------


def gqa_mixer_ext(cfg, kind, a, h, pos, cache, lay, axis, *, q_chunk=1024,
                  spos=None, anc=None):
    """Extension attention: h (B,C,d); pos (B,C) absolute positions of the
    chunk; cache k/v span the full per-slot buffer (non-windowed).

    Tree mode (speculative tree verification): `spos` (B,C) overrides
    the SCATTER positions (distinct cache slots pos+chunk-index) while
    `pos` keeps the tree positions (RoPE), and `anc` (C,C) switches
    chunk-internal visibility to the ancestor matrix (A.tree_mask)."""
    q, k, v = _qkv(cfg, a, h, lay, axis)
    q = apply_rope(q, pos, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, pos, cfg.rope_theta, cfg.rope_fraction)
    b, c = h.shape[:2]
    bi = jnp.arange(b)[:, None]
    wpos = pos if spos is None else spos
    if cfg.kv_dtype == "int8":
        kq, ks = A.kv_quantize(k)
        vq, vs = A.kv_quantize(v)
        cache = {"k": cache["k"].at[bi, wpos].set(kq),
                 "k_s": cache["k_s"].at[bi, wpos].set(ks),
                 "v": cache["v"].at[bi, wpos].set(vq),
                 "v_s": cache["v_s"].at[bi, wpos].set(vs)}
    else:
        cache = {"k": cache["k"].at[bi, wpos].set(k),
                 "v": cache["v"].at[bi, wpos].set(v)}
    kc, vc = _unpack_kv(cfg, cache, h.dtype)
    s_kv = kc.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(s_kv)[None], (b, s_kv))
    if anc is None:
        o = A.attention_any(q, kc, vc, pos, kv_pos, window=0,
                            q_chunk=q_chunk)
    else:
        o = A.attend(q, kc, vc, A.tree_mask(wpos[:, 0], anc, kv_pos))
    part = _mm(o.reshape(b, c, -1), a["wo"])
    return part, cache


def block_ext(cfg, kind, lay, p, x, pos, cache, *, drop: bool, tp: int,
              shard_idx, axis=MODEL_AXIS, q_chunk=1024, comm=None,
              spos=None, anc=None):
    """Chunked-prefill block: x (B,C,d), pos (B,C). Returns (out, cache)."""
    assert kind.mixer == "gqa" and kind.window == 0, kind
    h = _norm(x, p["ln1"], cfg, shared=False, axis=axis)
    h = column_entry(h, axis)
    part, cache = gqa_mixer_ext(cfg, kind, p["attn"], h, pos, cache, lay,
                                axis, q_chunk=q_chunk, spos=spos, anc=anc)
    out = _wire_post_mixer(cfg, kind, p, x, part, p["attn"].get("bo"),
                           drop=drop, tp=tp, shard_idx=shard_idx, axis=axis,
                           comm=comm)
    return out, cache


# ---------------------------------------------------------------------------
# Paged-cache mode: a segment's K/V caches are stacked physical page POOLS
# (L, P+1, ps, HkvL, dh) shared across slots, indexed through a page
# table — no contiguous per-slot view is ever materialized.  The layer
# scan (model.paged_step) carries the stacked pools, and layer `l` works
# on them in place: new tokens scatter straight into their pages at
# (l, page, offset), and attention reads layer l's K/V through the table
# (fused Pallas kernel on attn_backend="pallas", else the
# gather-only-the-table XLA path whose numerics are bit-identical to
# dense decode).  On the XLA path no pool-sized buffer is sliced out,
# copied or written back per step.  GQA full-causal fp-cache layers only
# (model.supports_paged_attention gates callers); other archs use the
# legacy gather/scatter fallback in runtime/forward.py.
# ---------------------------------------------------------------------------


def gqa_mixer_page(cfg, kind, a, h, pos, cache, layer, page_table, lay,
                   axis, depths=None, anc=None):
    """Paged attention over a chunk: h (B,C,d); pos (B,) absolute start
    position of each slot's chunk; cache {"k","v"} the segment's stacked
    page pools, of which this is layer `layer` (updated in place).

    Tree mode: `depths` (C,) replaces the contiguous chunk offsets for
    RoPE (token j sits at tree position pos+depths[j]) and `anc` (C,C)
    switches chunk-internal visibility to the ancestor matrix; the
    SCATTER stays chunk-contiguous (slot pos+j), matching the dense
    tree layout.  Tree chunks are tiny, so the XLA paged_attend path is
    used even under attn_backend="pallas"."""
    from repro.kernels import ops as KOPS
    q, k, v = _qkv(cfg, a, h, lay, axis)
    if depths is None:
        pos2 = pos[:, None] + jnp.arange(h.shape[1], dtype=jnp.int32)[None]
    else:
        pos2 = pos[:, None] + depths[None]
    q = apply_rope(q, pos2, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, pos2, cfg.rope_theta, cfg.rope_fraction)
    cache = {"k": KOPS.scatter_tokens_pages(cache["k"], layer, k,
                                            page_table, pos),
             "v": KOPS.scatter_tokens_pages(cache["v"], layer, v,
                                            page_table, pos)}
    if cfg.attn_backend == "pallas" and anc is None:
        import jax as _jax
        interp = _jax.default_backend() != "tpu"
        o = KOPS.paged_attention(q, cache["k"][layer], cache["v"][layer],
                                 page_table, pos, interpret=interp)
    else:
        # read layer `layer`'s pages through a flat (L*(P+1)) page index
        # into the stacked pools: indexing `pool[layer]` first makes XLA
        # materialize the layer's whole pool on the TPU.  Unallocated
        # (-1) entries stay -1, so paged_attend masks them as before.
        pn1 = cache["k"].shape[1]
        flat = jnp.where(page_table < 0, -1, page_table + layer * pn1)
        k_flat, v_flat = (cache[n].reshape((-1,) + cache[n].shape[2:])
                          for n in ("k", "v"))
        o = A.paged_attend(q, k_flat, v_flat, flat, pos, anc=anc)
    b, c = h.shape[:2]
    part = _mm(o.reshape(b, c, -1), a["wo"])
    return part, cache


def block_page(cfg, kind, lay, p, x, pos, cache, layer, page_table, *,
               drop: bool, tp: int, shard_idx, axis=MODEL_AXIS, comm=None,
               depths=None, anc=None):
    """Paged-cache block (decode C=1 or chunked-prefill extension C>1):
    x (B,C,d), pos (B,) chunk starts; cache the segment's stacked page
    pools, `layer` this block's index into them.  Returns (out, cache)."""
    assert kind.mixer == "gqa" and kind.window == 0, kind
    h = _norm(x, p["ln1"], cfg, shared=False, axis=axis)
    h = column_entry(h, axis)
    part, cache = gqa_mixer_page(cfg, kind, p["attn"], h, pos, cache, layer,
                                 page_table, lay, axis, depths=depths,
                                 anc=anc)
    out = _wire_post_mixer(cfg, kind, p, x, part, p["attn"].get("bo"),
                           drop=drop, tp=tp, shard_idx=shard_idx, axis=axis,
                           comm=comm)
    return out, cache
