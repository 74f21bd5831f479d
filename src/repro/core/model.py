"""Full-model forward/loss/prefill/decode, engine-agnostic.

The model is a list of decoder blocks (core/blocks.py) between a
vocab-parallel embedding and a vocab-parallel cross-entropy head
(Megatron-style: the vocab axis is sharded over the "model" mesh axis;
softmax max/sum and the label logit travel through psums).

Layers are grouped into SEGMENTS of equal (kind, spd-flag); each segment's
params are stacked on a leading layer axis and executed with lax.scan, so
the lowered HLO stays small at 80 layers.  `dual_mode` replaces the static
spd flag with a dynamic per-layer flag array (both wirings computed,
jnp.where-selected) — used by the sensitivity sweep so ALL plans share one
compilation.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig, SPDPlanConfig
from repro.core import blocks as B
from repro.core.layer_kinds import LayerKind, layer_kinds, plan_segments
from repro.models.common import layernorm, rmsnorm
from repro.parallel.collectives import (
    MODEL_AXIS, column_entry, comm_context, ledger_scale, pmax, shared_param,
    sync_output)
from repro.parallel.layout import REPLICATED, make_gqa_layout


# ---------------------------------------------------------------------------
# Init / specs / padding
# ---------------------------------------------------------------------------

def init_model(key, cfg: ModelConfig) -> dict:
    """Canonical (unpadded, unstacked) parameters."""
    dt = jnp.dtype(cfg.dtype)
    kinds = layer_kinds(cfg)
    emb_scale = 0.02
    p = {
        "emb": (jax.random.normal(jax.random.fold_in(key, 0),
                                  (cfg.vocab_size, cfg.d_model), jnp.float32)
                * emb_scale).astype(dt),
        "lnf": B._norm_init(cfg, cfg.d_model),
        "layers": [B.init_layer(jax.random.fold_in(key, 1000 + i), cfg, k)
                   for i, k in enumerate(kinds)],
    }
    if not cfg.tie_embeddings:
        p["head"] = (jax.random.normal(jax.random.fold_in(key, 1),
                                       (cfg.d_model, cfg.vocab_size),
                                       jnp.float32)
                     / np.sqrt(cfg.d_model)).astype(dt)
    if cfg.pos_emb == "learned":
        p["pos"] = (jax.random.normal(jax.random.fold_in(key, 2),
                                      (cfg.max_seq_len, cfg.d_model),
                                      jnp.float32) * emb_scale).astype(dt)
    if cfg.frontend_dim:
        p["front"] = (jax.random.normal(jax.random.fold_in(key, 3),
                                        (cfg.frontend_dim, cfg.d_model),
                                        jnp.float32)
                      / np.sqrt(cfg.frontend_dim)).astype(dt)
    return p


def vocab_pad(cfg: ModelConfig, tp: int) -> int:
    return -(-cfg.vocab_size // tp) * tp


def pad_model(p: dict, cfg: ModelConfig, tp: int) -> dict:
    """Canonical -> TP-layout (padded) params; layers stay a list."""
    kinds = layer_kinds(cfg)
    vp = vocab_pad(cfg, tp)
    out = {k: v for k, v in p.items() if k != "layers"}
    if vp != cfg.vocab_size:
        pad = vp - cfg.vocab_size
        out["emb"] = jnp.concatenate(
            [p["emb"], jnp.zeros((pad, cfg.d_model), p["emb"].dtype)], 0)
        if "head" in p:
            out["head"] = jnp.concatenate(
                [p["head"], jnp.zeros((cfg.d_model, pad), p["head"].dtype)], 1)
    out["layers"] = [B.quantize_layer_weights(B.pad_layer(lp, cfg, k, tp),
                                              cfg, k)
                     for lp, k in zip(p["layers"], kinds)]
    return out


def model_specs(cfg: ModelConfig) -> dict:
    kinds = layer_kinds(cfg)
    s = {"emb": 0, "lnf": B._norm_spec(cfg),
         "layers": [B.layer_specs(cfg, k) for k in kinds]}
    if not cfg.tie_embeddings:
        s["head"] = 1
    if cfg.pos_emb == "learned":
        s["pos"] = REPLICATED
    if cfg.frontend_dim:
        s["front"] = REPLICATED
    return s


def stack_segments(padded: dict, cfg: ModelConfig,
                   plan: SPDPlanConfig) -> dict:
    """Padded per-layer list -> per-segment stacked trees."""
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    out = {k: v for k, v in padded.items() if k != "layers"}
    out["segs"] = []
    for (start, length, kind, dropped) in segs:
        ls = padded["layers"][start:start + length]
        out["segs"].append(jax.tree.map(lambda *xs: jnp.stack(xs, 0), *ls))
    return out


def unstack_segments(stacked: dict, cfg: ModelConfig,
                     plan: SPDPlanConfig) -> dict:
    """Inverse of stack_segments: per-segment stacked trees -> padded
    per-layer list.  (Result is PADDED canonical for the tp it was built
    with; it equals true canonical whenever head/vocab padding is trivial
    at that tp.)"""
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    layers = [None] * cfg.n_layers
    for seg_i, (start, length, kind, dropped) in enumerate(segs):
        sv = stacked["segs"][seg_i]
        for j in range(length):
            layers[start + j] = jax.tree.map(lambda x, j=j: x[j], sv)
    out = {k: v for k, v in stacked.items() if k != "segs"}
    out["layers"] = layers
    return out


def stacked_specs(cfg: ModelConfig, plan: SPDPlanConfig) -> dict:
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    s = model_specs(cfg)
    out = {k: v for k, v in s.items() if k != "layers"}
    out["segs"] = [s["layers"][start] for (start, _, _, _) in segs]
    return out


# ---------------------------------------------------------------------------
# Embedding / head (vocab-parallel)
# ---------------------------------------------------------------------------

def embed_tokens(emb_shard, tokens, axis, shard_idx):
    """emb_shard (Vl, d); tokens (B,S) int32 -> (B,S,d) via masked psum."""
    vl = emb_shard.shape[0]
    local = tokens - shard_idx * vl
    valid = (local >= 0) & (local < vl)
    e = jnp.take(emb_shard, jnp.clip(local, 0, vl - 1), axis=0)
    e = jnp.where(valid[..., None], e, 0)
    return sync_output(e, axis, compressible=False)


def lm_logits(p, cfg, x, axis):
    """x (B,S,d) replicated -> shard-local logits (B,S,Vl) fp32."""
    x = column_entry(x, axis)
    w = p["emb"].T if cfg.tie_embeddings else p["head"]
    return (x @ w).astype(jnp.float32)


def serve_logits(p, cfg, x, axis, plan):
    """lm_logits for the SERVE paths (prefill/decode), honoring the comm
    policy's `logits_mode`: with a quantized mode the shard-local slice
    is put through the wire qdq and the final all-gather is ledger-logged
    at quantized bytes.  Applying the qdq identically on every shard (in
    both engines) keeps the gather-free greedy path and the full-gather
    sampled path in lockstep.  The CE/loss path keeps raw lm_logits —
    no gather happens there."""
    lg = lm_logits(p, cfg, x, axis)
    mode = plan.logits_mode if plan is not None else "exact"
    if mode != "exact":
        from repro.parallel.compression import (QUANT_BITS,
                                                quantized_gather_payload)
        lg = quantized_gather_payload(lg, axis, bits=QUANT_BITS[mode])
    return lg


def vocab_parallel_ce(logits, labels, mask, cfg, tp, axis, shard_idx):
    """Per-token CE with vocab sharded over `axis`.

    logits (B,S,Vl) fp32; labels (B,S) int32; mask (B,S) float.
    Returns (sum_ce, sum_mask) — caller normalizes (possibly after a DP
    psum for the global mean)."""
    vl = logits.shape[-1]
    gcol = shard_idx * vl + jnp.arange(vl)
    logits = jnp.where((gcol < cfg.vocab_size)[None, None], logits, -1e30)
    m = pmax(jax.lax.stop_gradient(jnp.max(logits, -1)), axis)   # (B,S)
    se = sync_output(jnp.sum(jnp.exp(logits - m[..., None]), -1), axis,
                     compressible=False)
    lbl_local = labels - shard_idx * vl
    ok = (lbl_local >= 0) & (lbl_local < vl)
    lbl_logit = jnp.take_along_axis(
        logits, jnp.clip(lbl_local, 0, vl - 1)[..., None], -1)[..., 0]
    lbl_logit = sync_output(jnp.where(ok, lbl_logit, 0.0), axis,
                            compressible=False)
    ce = jnp.log(se) + m - lbl_logit                          # (B,S)
    return jnp.sum(ce * mask), jnp.sum(mask)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _gqa_layout_or_none(cfg, tp):
    if cfg.family == "ssm" or cfg.mla is not None:
        return None
    return make_gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp)


def forward_seq(cfg, stacked, plan: SPDPlanConfig, tokens, *, tp, axis=MODEL_AXIS,
                embeds=None, q_chunk=1024, want_cache=False, remat=False,
                dual_flags=None, fsdp=None):
    """Sequence forward (train / prefill).

    tokens (B,S_tok); embeds (B,Flen,frontend_dim) for modality-stub archs.
    Returns (hidden (B,S,d), aux_loss, caches, mask_prefix_len).
    `dual_flags` (L,) float: dynamic-SPD selection (simtp algorithms only;
    requires a single all-layers plan segmentation).
    """
    shard_idx = jax.lax.axis_index(axis)
    lay = _gqa_layout_or_none(cfg, tp)
    if fsdp is not None:
        from repro.parallel.fsdp import gather_leaf
        emb = gather_leaf(stacked["emb"], fsdp["emb"])
    else:
        emb = stacked["emb"]
    x = embed_tokens(emb, tokens, axis, shard_idx)
    prefix = 0
    if cfg.frontend_dim and embeds is not None:
        front_w = stacked["front"]
        if fsdp is not None:
            from repro.parallel.fsdp import gather_leaf
            front_w = gather_leaf(front_w, fsdp["front"])
        front = (embeds.astype(x.dtype) @ front_w)
        x = jnp.concatenate([front, x], axis=1)
        prefix = embeds.shape[1]
    b, s = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if cfg.pos_emb == "learned":
        pos_w = stacked["pos"]
        if fsdp is not None:
            from repro.parallel.fsdp import gather_leaf
            pos_w = gather_leaf(pos_w, fsdp["pos"])
        x = x + pos_w[:s][None]

    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    aux_total = jnp.zeros((), jnp.float32)
    caches = []
    li = 0
    for seg_i, (start, length, kind, dropped) in enumerate(segs):
        sp = stacked["segs"][seg_i]

        if dual_flags is None:
            def body(xc, layer_p, kind=kind, dropped=dropped, seg_i=seg_i,
                     comm=plan.block_mode(start)):
                if fsdp is not None:
                    from repro.parallel.fsdp import gather_tree
                    layer_p = gather_tree(layer_p, fsdp["segs"][seg_i],
                                          shift=-1)
                out, aux, cache = B.block_seq(
                    cfg, kind, lay, layer_p, xc, pos, drop=dropped, tp=tp,
                    shard_idx=shard_idx, axis=axis, want_cache=want_cache,
                    q_chunk=q_chunk, comm=comm)
                return out, (aux, cache)
        else:
            flags = jax.lax.dynamic_slice_in_dim(dual_flags, start, length)

            def body(xc, lp_flag, kind=kind):
                layer_p, flag = lp_flag
                out_tp, aux_tp, _ = B.block_seq(
                    cfg, kind, lay, layer_p, xc, pos, drop=False, tp=tp,
                    shard_idx=shard_idx, axis=axis, q_chunk=q_chunk)
                if cfg.spd_applicable:
                    out_sp, aux_sp, _ = B.block_seq(
                        cfg, kind, lay, layer_p, xc, pos, drop=True, tp=tp,
                        shard_idx=shard_idx, axis=axis, q_chunk=q_chunk)
                    out = jnp.where(flag > 0.5, out_sp, out_tp)
                    aux = jnp.where(flag > 0.5, aux_sp, aux_tp)
                else:
                    out, aux = out_tp, aux_tp
                return out, (aux, None)

        if remat:
            body = jax.checkpoint(body)

        xs = (sp, flags) if dual_flags is not None else sp

        with ledger_scale(length), comm_context(block=start, phase="prefill"):
            x, (auxs, cache) = jax.lax.scan(body, x, xs)
        aux_total = aux_total + jnp.sum(auxs)
        caches.append(cache)
        li += length

    lnf = stacked["lnf"]
    if fsdp is not None:
        from repro.parallel.fsdp import gather_tree
        lnf = gather_tree(lnf, fsdp["lnf"])
    x = (layernorm(x, lnf["w"], lnf["b"], cfg.norm_eps)
         if cfg.norm == "layernorm"
         else rmsnorm(x, lnf["w"], cfg.norm_eps))
    return x, aux_total, (caches if want_cache else None), prefix


def loss_fn(cfg, stacked, plan, batch, *, tp, axis=MODEL_AXIS, q_chunk=1024,
            remat=False, dual_flags=None, aux_coef=0.01, fsdp=None):
    """batch: {"tokens" (B,S), "labels" (B,S), "mask" (B,S)[, "embeds"]}.

    Returns (loss_local_sum_normalized, metrics).  The caller is
    responsible for DP-mean semantics: we return (sum_ce, n_tok) psum-able
    pieces inside metrics, and a local loss already divided by the LOCAL
    token count for single-shard use.
    """
    x, aux, _, prefix = forward_seq(
        cfg, stacked, plan, batch["tokens"], tp=tp, axis=axis,
        embeds=batch.get("embeds"), q_chunk=q_chunk, remat=remat,
        dual_flags=dual_flags, fsdp=fsdp)
    shard_idx = jax.lax.axis_index(axis)
    head_view = stacked
    if fsdp is not None:
        from repro.parallel.fsdp import gather_leaf
        head_view = dict(stacked)
        if cfg.tie_embeddings:
            head_view["emb"] = gather_leaf(stacked["emb"], fsdp["emb"])
        else:
            head_view["head"] = gather_leaf(stacked["head"], fsdp["head"])
    logits = lm_logits(head_view, cfg, x[:, prefix:], axis)
    sum_ce, n_tok = vocab_parallel_ce(
        logits, batch["labels"], batch["mask"].astype(jnp.float32), cfg, tp,
        axis, shard_idx)
    loss = sum_ce / jnp.maximum(n_tok, 1.0) + aux_coef * aux
    return loss, {"sum_ce": sum_ce, "n_tok": n_tok, "aux": aux}


# ---------------------------------------------------------------------------
# Prefill / decode (serving)
# ---------------------------------------------------------------------------

def prefill(cfg, stacked, plan, tokens, *, tp, axis=MODEL_AXIS, q_chunk=1024,
            embeds=None, cache_len: int = 0, lengths=None):
    """Returns (next-token logits (B,Vl) fp32 shard-local, caches).

    `cache_len` > 0 pads the sequence axis of KV/latent caches to a fixed
    decode buffer length (rolling windowed caches keep their window).
    `lengths` (B,): real prompt lengths for right-padded batches — logits
    are taken at position lengths-1; decode then starts at pos=lengths and
    overwrites the padded cache slots before they ever become causally
    visible (exactness test: test_server.py)."""
    x, _, caches, prefix = forward_seq(
        cfg, stacked, plan, tokens, tp=tp, axis=axis, embeds=embeds,
        q_chunk=q_chunk, want_cache=True)
    if lengths is None:
        xq = x[:, -1:]
    else:
        idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
        xq = jnp.take_along_axis(x, idx[:, None, None].astype(jnp.int32)
                                 .repeat(x.shape[-1], -1), axis=1)
    logits = serve_logits(stacked, cfg, xq, axis, plan)[:, 0]
    if cache_len:
        def pad_seq(c, seq_axis, target):
            cur = c.shape[seq_axis]
            if cur >= target:
                return c
            pads = [(0, 0)] * c.ndim
            pads[seq_axis] = (0, target - cur)
            return jnp.pad(c, pads)

        segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
        out = []
        for (start, length, kind, dropped), seg in zip(segs, caches):
            seg = dict(seg)
            if kind.mixer == "mla":
                seg["c"] = pad_seq(seg["c"], 2, cache_len)
                seg["kr"] = pad_seq(seg["kr"], 2, cache_len)
            elif kind.mixer in ("gqa", "hybrid"):
                target = kind.window if kind.window else cache_len
                for kk in ("k", "v", "k_s", "v_s"):
                    if kk in seg:
                        seg[kk] = pad_seq(seg[kk], 2, target)
            out.append(seg)
        caches = out
    return logits, caches


def decode_step(cfg, stacked, plan, tokens, pos, caches, *, tp,
                axis=MODEL_AXIS):
    """One decode step.  tokens (B,1), pos (B,), caches per segment.

    Returns (logits (B,Vl) fp32 shard-local, new caches)."""
    shard_idx = jax.lax.axis_index(axis)
    lay = _gqa_layout_or_none(cfg, tp)
    x = embed_tokens(stacked["emb"], tokens, axis, shard_idx)
    if cfg.pos_emb == "learned":
        x = x + jnp.take(stacked["pos"], pos, axis=0)[:, None]
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    new_caches = []
    for seg_i, (start, length, kind, dropped) in enumerate(segs):
        sp = stacked["segs"][seg_i]
        cache_seg = caches[seg_i]

        def body(xc, xs_i, kind=kind, dropped=dropped,
                 comm=plan.block_mode(start)):
            layer_p, cache = xs_i
            out, new_cache = B.block_dec(
                cfg, kind, lay, layer_p, xc, pos, cache, drop=dropped,
                tp=tp, shard_idx=shard_idx, axis=axis, comm=comm)
            return out, new_cache

        with ledger_scale(length), comm_context(block=start, phase="decode"):
            x, nc = jax.lax.scan(body, x, (sp, cache_seg))
        new_caches.append(nc)
    x = (layernorm(x, stacked["lnf"]["w"], stacked["lnf"]["b"], cfg.norm_eps)
         if cfg.norm == "layernorm"
         else rmsnorm(x, stacked["lnf"]["w"], cfg.norm_eps))
    logits = serve_logits(stacked, cfg, x, axis, plan)[:, 0]
    return logits, new_caches


# ---------------------------------------------------------------------------
# Cache allocation (shapes for serve paths & dry-run input specs)
# ---------------------------------------------------------------------------

def cache_struct(cfg, plan: SPDPlanConfig, batch: int, seq_len: int, tp: int):
    """ShapeDtypeStructs of the decode caches (shard-LOGICAL, i.e. global
    shapes whose head axes carry the full padded head counts; engines shard
    or split the head axis)."""
    dt = jnp.dtype(cfg.dtype)
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    lay = _gqa_layout_or_none(cfg, tp)
    out = []
    for (start, length, kind, dropped) in segs:
        if kind.mixer == "ssm" or kind.mixer == "hybrid":
            s = cfg.ssm
            h = B.ssm_heads(cfg)
            hp = (make_gqa_layout(cfg.n_heads, cfg.n_kv_heads, tp).h_pad
                  if kind.mixer == "hybrid" else -(-h // tp) * tp)
            d_in = hp * s.head_dim
            gn_ = s.n_groups * s.d_state
            ssm_c = {
                "state": jax.ShapeDtypeStruct(
                    (length, batch, hp, s.head_dim, s.d_state), dt),
                "conv": {
                    "x": jax.ShapeDtypeStruct(
                        (length, batch, s.d_conv - 1, d_in), dt),
                    "bc": jax.ShapeDtypeStruct(
                        (length, batch, s.d_conv - 1, 2 * gn_), dt),
                },
            }
        if kind.mixer == "ssm":
            out.append(ssm_c)
            continue
        if kind.mixer == "mla":
            m = cfg.mla
            out.append({
                "c": jax.ShapeDtypeStruct(
                    (length, batch, seq_len, m.kv_lora_rank), dt),
                "kr": jax.ShapeDtypeStruct(
                    (length, batch, seq_len, m.qk_rope_head_dim), dt),
            })
            continue
        w = kind.window
        s_kv = min(w, seq_len) if w else seq_len
        if cfg.kv_dtype == "int8":
            kv = {
                "k": jax.ShapeDtypeStruct(
                    (length, batch, s_kv, lay.kv_layout, cfg.d_head),
                    jnp.int8),
                "k_s": jax.ShapeDtypeStruct(
                    (length, batch, s_kv, lay.kv_layout), jnp.bfloat16),
                "v": jax.ShapeDtypeStruct(
                    (length, batch, s_kv, lay.kv_layout, cfg.d_head),
                    jnp.int8),
                "v_s": jax.ShapeDtypeStruct(
                    (length, batch, s_kv, lay.kv_layout), jnp.bfloat16),
            }
        else:
            kv = {
                "k": jax.ShapeDtypeStruct(
                    (length, batch, s_kv, lay.kv_layout, cfg.d_head), dt),
                "v": jax.ShapeDtypeStruct(
                    (length, batch, s_kv, lay.kv_layout, cfg.d_head), dt),
            }
        if kind.mixer == "hybrid":
            kv.update(ssm_c)
        out.append(kv)
    return out


def cache_pageable_tree(cfg, plan: SPDPlanConfig):
    """Which cache leaves get PAGED (bool tree matching cache_struct).

    Paged: leaves with a full-length sequence axis at position 2 in the
    shard-logical (layer, batch, seq, ...) layout — GQA/hybrid K/V (and
    int8 scales) on non-windowed layers, MLA latents.  Dense per-slot:
    rolling-window KV (already bounded to `window`), SSM state, and conv
    tails (no sequence axis to page)."""
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    out = []
    for (start, length, kind, dropped) in segs:
        ssm_c = {"state": False, "conv": {"x": False, "bc": False}}
        if kind.mixer == "ssm":
            out.append(ssm_c)
            continue
        if kind.mixer == "mla":
            out.append({"c": True, "kr": True})
            continue
        pageable = kind.window == 0
        kv = {"k": pageable, "v": pageable}
        if cfg.kv_dtype == "int8":
            kv.update({"k_s": pageable, "v_s": pageable})
        if kind.mixer == "hybrid":
            kv.update(ssm_c)
        out.append(kv)
    return out


def paged_cache_struct(cfg, plan: SPDPlanConfig, batch: int, seq_len: int,
                       tp: int, *, page_size: int, num_pages: int):
    """cache_struct with pageable leaves' (batch, seq) axes replaced by
    (num_pages + 1, page_size); the extra page is the trash page (see
    runtime/paging.py).  Non-pageable leaves keep dense (batch, ...)."""
    structs = cache_struct(cfg, plan, batch, seq_len, tp)
    flags = cache_pageable_tree(cfg, plan)

    def one(f, s):
        if not f:
            return s
        shp = (s.shape[0], num_pages + 1, page_size) + s.shape[3:]
        return jax.ShapeDtypeStruct(shp, s.dtype)

    return [jax.tree.map(one, f, s) for f, s in zip(flags, structs)]


def supports_chunked_prefill(cfg) -> bool:
    """Chunked prefill (prefill_chunk) covers full-causal GQA stacks;
    windowed/MLA/SSM/hybrid layers and modality-prefix archs fall back to
    one-shot prefill."""
    from repro.core.layer_kinds import layer_kinds
    kinds = layer_kinds(cfg)
    return (not cfg.frontend_dim
            and all(k.mixer == "gqa" and k.window == 0 for k in kinds))


def prefill_chunk(cfg, stacked, plan, tokens, start, caches, *, tp,
                  axis=MODEL_AXIS, lengths=None, q_chunk=1024):
    """One chunk of incremental prefill (see supports_chunked_prefill).

    tokens (B,C) at absolute positions [start, start+C); caches in
    decode_step layout, sequence axes sized to the full decode buffer.
    Returns (logits (B,Vl) fp32 shard-local taken at position
    clip(lengths-1-start, 0, C-1) within the chunk — meaningful only for
    the chunk containing lengths-1 — and the updated caches)."""
    shard_idx = jax.lax.axis_index(axis)
    lay = _gqa_layout_or_none(cfg, tp)
    b, c = tokens.shape
    pos = jnp.broadcast_to(start + jnp.arange(c)[None], (b, c))
    x = embed_tokens(stacked["emb"], tokens, axis, shard_idx)
    if cfg.pos_emb == "learned":
        x = x + jnp.take(stacked["pos"], pos[0], axis=0)[None]
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    new_caches = []
    for seg_i, (s0, length, kind, dropped) in enumerate(segs):
        sp = stacked["segs"][seg_i]
        cache_seg = caches[seg_i]

        def body(xc, xs_i, kind=kind, dropped=dropped,
                 comm=plan.block_mode(s0)):
            layer_p, cache = xs_i
            out, nc = B.block_ext(cfg, kind, lay, layer_p, xc, pos, cache,
                                  drop=dropped, tp=tp, shard_idx=shard_idx,
                                  axis=axis, q_chunk=q_chunk, comm=comm)
            return out, nc

        with ledger_scale(length), comm_context(block=s0, phase="prefill"):
            x, nc = jax.lax.scan(body, x, (sp, cache_seg))
        new_caches.append(nc)
    x = (layernorm(x, stacked["lnf"]["w"], stacked["lnf"]["b"], cfg.norm_eps)
         if cfg.norm == "layernorm"
         else rmsnorm(x, stacked["lnf"]["w"], cfg.norm_eps))
    if lengths is None:
        idx = jnp.full((b,), c - 1, jnp.int32)
    else:
        idx = jnp.clip(lengths - 1 - start, 0, c - 1).astype(jnp.int32)
    xq = jnp.take_along_axis(x, idx[:, None, None].repeat(x.shape[-1], -1),
                             axis=1)
    logits = serve_logits(stacked, cfg, xq, axis, plan)[:, 0]
    return logits, new_caches


def supports_spec_decode(cfg) -> bool:
    """Self-speculative decoding needs (a) a second sync point per block
    to drop (spd_applicable) and (b) the cache-extension forward that
    scores several drafted tokens in one step (same coverage as chunked
    prefill: full-causal GQA stacks)."""
    return cfg.spd_applicable and supports_chunked_prefill(cfg)


def verify_step(cfg, stacked, plan, tokens, pos, caches, *, tp,
                axis=MODEL_AXIS, q_chunk=1024, tree=None):
    """Multi-token verify forward for speculative decoding.

    tokens (B, C): the last accepted token followed by C-1 drafted
    tokens; pos (B,): per-row absolute position of tokens[:, 0] (rows
    may sit at DIFFERENT positions — this is the decode-time analog of
    prefill_chunk, which assumes one scalar chunk start).  Writes each
    token's KV at pos+j and returns logits at EVERY chunk position
    ((B, C, Vl) fp32 shard-local) plus the updated caches: logits[:, j]
    scores the token after tokens[:, j], which is what acceptance needs.

    `tree=(depths, anc)` verifies a draft TREE instead of a chain:
    token j keeps cache slot pos+j (distinct scatter positions) but
    sits at tree position pos+depths[j] (RoPE + logits semantics), and
    attends committed history plus its in-chunk ancestors anc[j]
    (spec/verify.tree_layout builds the layout; docs/speculative.md).
    tree=None is bit-identical to the pre-tree chain path.

    Rollback contract: rejected-suffix KV entries stay in the cache but
    are never causally visible (attention masks kv_pos <= q_pos) and are
    overwritten as soon as the position counter passes them again — so
    dense rollback is just the scheduler rewinding pos (docs/speculative.md).
    """
    shard_idx = jax.lax.axis_index(axis)
    lay = _gqa_layout_or_none(cfg, tp)
    b, c = tokens.shape
    spos2 = pos[:, None] + jnp.arange(c, dtype=pos.dtype)[None]    # (B, C)
    if tree is None:
        pos2, spos, anc = spos2, None, None
    else:
        depths, anc = tree
        pos2 = pos[:, None] + jnp.asarray(depths, pos.dtype)[None]
        spos = spos2
        anc = jnp.asarray(anc, bool)
    x = embed_tokens(stacked["emb"], tokens, axis, shard_idx)
    if cfg.pos_emb == "learned":
        x = x + jnp.take(stacked["pos"], pos2, axis=0)
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    new_caches = []
    for seg_i, (s0, length, kind, dropped) in enumerate(segs):
        sp = stacked["segs"][seg_i]
        cache_seg = caches[seg_i]

        def body(xc, xs_i, kind=kind, dropped=dropped,
                 comm=plan.block_mode(s0)):
            layer_p, cache = xs_i
            out, nc = B.block_ext(cfg, kind, lay, layer_p, xc, pos2, cache,
                                  drop=dropped, tp=tp, shard_idx=shard_idx,
                                  axis=axis, q_chunk=q_chunk, comm=comm,
                                  spos=spos, anc=anc)
            return out, nc

        with ledger_scale(length), comm_context(block=s0, phase="verify"):
            x, nc = jax.lax.scan(body, x, (sp, cache_seg))
        new_caches.append(nc)
    x = (layernorm(x, stacked["lnf"]["w"], stacked["lnf"]["b"], cfg.norm_eps)
         if cfg.norm == "layernorm"
         else rmsnorm(x, stacked["lnf"]["w"], cfg.norm_eps))
    logits = serve_logits(stacked, cfg, x, axis, plan)
    return logits, new_caches


def supports_paged_attention(cfg) -> bool:
    """The fused paged forward (paged_step / blocks.block_page) covers
    full-causal GQA stacks with fp KV caches: every cache leaf is a pure
    {"k","v"} page pool.  int8 KV (extra scale leaves), windowed, MLA,
    SSM, hybrid, and modality-prefix archs use the legacy
    gather->dense-step->scatter fallback in runtime/forward.py."""
    return supports_chunked_prefill(cfg) and cfg.kv_dtype != "int8"


def paged_step(cfg, stacked, plan, tokens, pos, caches, page_table, *, tp,
               axis=MODEL_AXIS, tree=None):
    """Fused paged forward: decode (C=1), chunked-prefill extension, and
    speculative verify all in one shape family.  `tree=(depths, anc)`
    switches the chunk to tree verification exactly as in verify_step
    (scatter stays chunk-contiguous; RoPE/visibility follow the tree).

    tokens (B, C) at per-row absolute positions pos (B,); caches per
    segment hold paged K/V pools (length, P+1, ps, HkvL, dh) shared
    across slots; page_table (B, n) int32 (-1 = unallocated) maps logical
    page j of slot b to a physical page.  Each segment's layer scan
    carries its stacked pools and updates them in place: layer l
    scatters its new K/V straight into the slot's pages at (l, page,
    offset) and attention reads layer l's pages through the table
    (blocks.gqa_mixer_page), so neither a contiguous per-slot cache view
    nor a per-layer copy of a pool is ever materialized; the carried
    pools come out as the new caches (donated in, aliased out).
    Returns (logits (B, C, Vl) fp32 shard-local — entry j scores the
    token after tokens[:, j] — and the updated caches).

    Rollback contract matches verify_step: rejected-suffix K/V stays in
    the slot's pages but is never causally visible, and is overwritten
    when the position counter passes it again (pages are slot-private at
    write positions — COW guarantees shared prefix pages are read-only,
    runtime/paging.py)."""
    shard_idx = jax.lax.axis_index(axis)
    lay = _gqa_layout_or_none(cfg, tp)
    b, c = tokens.shape
    if tree is None:
        depths, anc = None, None
        pos2 = pos[:, None] + jnp.arange(c, dtype=pos.dtype)[None]  # (B, C)
    else:
        depths, anc = tree
        depths = jnp.asarray(depths, pos.dtype)
        anc = jnp.asarray(anc, bool)
        pos2 = pos[:, None] + depths[None]
    x = embed_tokens(stacked["emb"], tokens, axis, shard_idx)
    if cfg.pos_emb == "learned":
        x = x + jnp.take(stacked["pos"], pos2, axis=0)
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    new_caches = []
    for seg_i, (s0, length, kind, dropped) in enumerate(segs):
        sp = stacked["segs"][seg_i]

        def body(carry, xs_i, kind=kind, dropped=dropped,
                 comm=plan.block_mode(s0)):
            xc, pools = carry
            layer_p, layer = xs_i
            out, pools = B.block_page(cfg, kind, lay, layer_p, xc, pos,
                                      pools, layer, page_table, drop=dropped,
                                      tp=tp, shard_idx=shard_idx, axis=axis,
                                      comm=comm, depths=depths, anc=anc)
            return (out, pools), None

        with ledger_scale(length), comm_context(block=s0, phase="decode"):
            (x, nc), _ = jax.lax.scan(body, (x, caches[seg_i]),
                                      (sp, jnp.arange(length)))
        new_caches.append(nc)
    x = (layernorm(x, stacked["lnf"]["w"], stacked["lnf"]["b"], cfg.norm_eps)
         if cfg.norm == "layernorm"
         else rmsnorm(x, stacked["lnf"]["w"], cfg.norm_eps))
    logits = serve_logits(stacked, cfg, x, axis, plan)
    return logits, new_caches


def cache_specs_tree(cfg, plan: SPDPlanConfig, tp: int = 0):
    """Split-axis ints for each cache leaf (REPLICATED for MLA latent)."""
    segs = plan_segments(cfg, plan.drop_mask, plan.qmodes)
    out = []
    for (start, length, kind, dropped) in segs:
        ssm_c = {"state": 2, "conv": {"x": 3, "bc": REPLICATED}}
        if kind.mixer == "ssm":
            out.append(ssm_c)
            continue
        if kind.mixer == "mla":
            out.append({"c": REPLICATED, "kr": REPLICATED})
            continue
        kv = {"k": 3, "v": 3}
        if cfg.kv_dtype == "int8":
            kv.update({"k_s": 3, "v_s": 3})
        if kind.mixer == "hybrid":
            kv.update(ssm_c)
        out.append(kv)
    return out
