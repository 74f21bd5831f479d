"""Smoke run of the served path on a TPU: qwen3-1.7b at its published
widths, bf16, random weights drawn from --seed.

    python chip_smoke.py             one chip, one process
    python chip_smoke.py --chips 4   one process driving four chips

One chip: `LLM.load(engine="shard", tp=1)` with the paged KV cache,
chunked prefill and the automatic prefix cache serves 9 requests
(64-1024 prompt tokens, two sharing a 512-token prefix, 32 new tokens
each) through `LLM.generate`; then the Pallas attention kernels
(`flash_attention` for prefill, `paged_flash_attention` for decode) run
on the same placed weights and are compared with the XLA path.

Four chips: the model at TP=4 on a (1, 4) mesh against TP=1 on one of
the same chips (prefill logits and greedy streams), then TP=4 with SPD
dropping half the attention syncs and the kept syncs at int8, which
must serve the same requests; the collectives each plan's compiled
decode step executes are read from its HLO.

Every earlier line of stdout is a JSON record of what a phase saw:
compile seconds, compile-cache hits, peak device memory, request and
token counts, agreement with the reference.  None of them is a
benchmark.  The last line is {"ok": true, "device": {...}}.  Any failed
check raises, and the script exits non-zero without that line; it also
fails when JAX's first device is not a TPU — there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen3-1.7b"
PAGE = 16
CHUNK = 256
MAX_NEW = 32
CACHE_LEN = 1024 + MAX_NEW          # longest prompt + its decode budget
MAX_BATCH = 8
# bf16 tolerance on last-position logits, as relative L2 error
# ||a - b|| / ||b||: bf16 keeps 8 mantissa bits (2^-8 = 0.4% per
# rounding), and 28 layers of differently ordered bf16 rounding compound
# to a few percent at most; a wrong kernel or a wrong shard layout is
# off by order one
LOGITS_RTOL = 0.05
# greedy streams at TP=4 and TP=1 must agree on this many first tokens,
# or part at a near-tie (`parting`)
AGREE_TOKENS = 4


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


class Phase:
    """Wall and compile seconds, compile-cache hits and misses, and the
    devices' peak memory for one phase, from JAX's monitoring events."""

    hits = misses = 0
    compile_s = 0.0

    COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                      "/jax/core/compile/jaxpr_to_mlir_module_duration",
                      "/jax/core/compile/backend_compile_duration")

    @classmethod
    def install(cls):
        import jax

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                cls.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                cls.misses += 1

        def on_duration(event, duration, **kw):
            if event in cls.COMPILE_EVENTS:
                cls.compile_s += duration

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def __init__(self, name: str, devices):
        self.name, self.devices = name, devices

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = (Phase.compile_s, Phase.hits, Phase.misses)
        self.rec = {"phase": self.name}
        return self.rec

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        self.rec.update({
            "wall_s": round(time.perf_counter() - self.t0, 3),
            "compile_s": round(Phase.compile_s - self.c0[0], 3),
            "cache_hits": Phase.hits - self.c0[1],
            "cache_misses": Phase.misses - self.c0[2],
            "peak_bytes_in_use": [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in self.devices]})
        emit(self.rec)
        return False


def make_prompts(seed: int, vocab: int):
    """Two prompts sharing a 512-token prefix (first, so both are admitted
    in one step and the second hits the first's pages), then prompts of
    64-1024 tokens."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, 512)
    prompts = [np.concatenate([shared, rng.integers(0, vocab, n)])
               for n in (40, 72)]
    prompts += [rng.integers(0, vocab, n)
                for n in (64, 128, 200, 333, 480, 700, 1024)]
    return [p.astype(np.int32) for p in prompts]


def load(tp: int, seed: int, **kw):
    from repro.api import LLM
    return LLM.load(ARCH, engine="shard", tp=tp, seed=seed,
                    page_size=PAGE, num_pages=MAX_BATCH * CACHE_LEN // PAGE,
                    prefill_chunk=CHUNK, cache_len=CACHE_LEN,
                    max_batch=MAX_BATCH, **kw)


def serve(llm, prompts, max_new: int, rec: dict):
    """Generate to completion; check every request finished with
    `max_new` in-vocabulary tokens.  Returns the token lists."""
    from repro.api import SamplingParams
    outs = llm.generate(prompts, SamplingParams(max_new=max_new))
    toks = [o.token_ids for o in outs]
    vocab = llm.cfg.vocab_size
    check(all(o.finish_reason == "length" for o in outs)
          and all(len(t) == max_new for t in toks),
          f"not every request completed: {[len(t) for t in toks]}")
    check(all(0 <= x < vocab for t in toks for x in t),
          "a generated token is outside the vocabulary")
    kv = llm.serve().kv
    rec.update({"requests": len(outs),
                "prompt_tokens": int(sum(len(p) for p in prompts)),
                "generated_tokens": int(sum(len(t) for t in toks)),
                "prefix_hits": kv.prefix_hits,
                "prefix_tokens_reused": kv.prefix_tokens_reused,
                "preemptions": llm.serve().n_preemptions})
    return toks


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare_logits(rec: dict, key: str, got, ref) -> None:
    err = rel_err(got, ref)
    rec[key] = {"rel_l2": err, "tol": LOGITS_RTOL,
                "max_abs": float(np.max(np.abs(np.asarray(got, np.float64)
                                               - np.asarray(ref)))),
                "argmax_equal": bool(np.array_equal(
                    np.argmax(got, -1), np.argmax(ref, -1)))}
    check(np.isfinite(np.asarray(got)).all(), f"{key}: non-finite logits")
    check(err <= LOGITS_RTOL, f"{key}: relative L2 {err} > {LOGITS_RTOL}")


def prefill_logits(llm, engine, seqs):
    """Last-position logits (fp32, a row per sequence) and the caches of
    one plain prefill of `seqs`, right-padded into one batch."""
    import jax.numpy as jnp
    toks = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    lg, caches = engine.prefill(
        llm.params, jnp.asarray(toks), cache_len=CACHE_LEN,
        lengths=jnp.asarray([len(s) for s in seqs], jnp.int32))
    return np.asarray(lg, np.float32), caches


def parting(ref, got, x: int, y: int) -> dict:
    """Where two greedy streams first differ, x the reference's token and
    y the other engine's, given both engines' logits on the context they
    share: the reference's margin for x over y against the largest logit
    difference between the engines.  A margin within twice that is a
    near-tie that rounding alone can flip (a random-weight model's top
    logits sit close together); a broken path picks a token far down
    the reference's ranking."""
    margin = float(ref[x] - ref[y])
    diff = float(np.max(np.abs(got - ref)))
    return {"margin": margin, "max_abs_diff": diff,
            "rank_in_ref": int(np.sum(ref > ref[y])),
            "near_tie": abs(margin) <= 2 * diff}


def one_chip(args, devices) -> None:
    import jax.numpy as jnp
    from repro.config.base import replace
    from repro.runtime.engines import Engine

    with Phase("load_tp1", devices) as rec:
        llm = load(1, args.seed)
        rec["params"] = llm.cfg.param_count()
    prompts = make_prompts(args.seed, llm.cfg.vocab_size)

    with Phase("serve_tp1", devices) as rec:
        serve(llm, prompts, MAX_NEW, rec)
        check(rec["prefix_hits"] >= 1, "the prefix cache never hit")

    # the Pallas attention path on the same placed weights: a second
    # engine over the same backend and plan, differing only in
    # attn_backend (which does not change the parameter layout)
    pallas = Engine(replace(llm.cfg, attn_backend="pallas"), llm.plan,
                    llm.engine.backend, q_chunk=llm.q_chunk)
    with Phase("pallas_vs_xla", devices) as rec:
        prompt = prompts[-1]
        ref, caches = prefill_logits(llm, llm.engine, [prompt])
        got, _ = prefill_logits(llm, pallas, [prompt])
        compare_logits(rec, "prefill_flash_attention", got, ref)
        # one decode step through the page table: both engines read the
        # same prefilled K/V, inserted into a one-slot pool each
        n = CACHE_LEN // PAGE
        table = jnp.arange(n, dtype=jnp.int32)[None]
        tok = jnp.asarray([[int(np.argmax(ref[0]))]], jnp.int32)
        pos = jnp.asarray([len(prompt)], jnp.int32)
        lgs = []
        for eng in (llm.engine, pallas):
            pool = eng.blank_paged_caches(1, CACHE_LEN, page_size=PAGE,
                                          num_pages=n)
            pool = eng.insert_paged(pool, caches, 0, np.arange(n))
            _, lg, _ = eng.decode_paged_with_logits(llm.params, tok, pos,
                                                    table, pool)
            lgs.append(np.asarray(lg, np.float32))
        compare_logits(rec, "decode_paged_flash_attention", lgs[1], lgs[0])


def decode_collectives(llm) -> dict:
    """Collectives the compiled greedy paged-decode step executes per
    call, split by the element type each moves, read from its HLO
    (lowering does not consume the donated cache, and the compiled
    program comes from the compile cache)."""
    import jax.numpy as jnp
    from repro.parallel.hlo import collective_counts

    kv = llm.serve().kv
    b = MAX_BATCH
    step = llm.engine._decode_paged(False)
    txt = step.lower(llm.params, jnp.zeros((b, 1), jnp.int32),
                     jnp.zeros((b,), jnp.int32), kv._table(),
                     kv.pcaches).compile().as_text()
    return {op: c for op, c in collective_counts(txt).items()
            if c["sites"] or op in ("all-reduce", "reduce-scatter")}


def four_chips(args, devices) -> None:
    with Phase("load_tp4_tp1", devices) as rec:
        llm4 = load(4, args.seed)
        llm1 = load(1, args.seed, params=llm4.canonical)
        rec["tp4_mesh"] = [[d.id, getattr(d, "coords", None)]
                           for d in llm4.mesh.devices.ravel()]
        rec["tp1_mesh"] = [d.id for d in llm1.mesh.devices.ravel()]
    prompts = make_prompts(args.seed, llm4.cfg.vocab_size)
    # the shared-prefix pair plus the shortest and the longest prompt
    prompts = prompts[:3] + prompts[-1:]
    new = 2 * AGREE_TOKENS

    with Phase("tp4_vs_tp1", devices) as rec:
        r1, r4 = {}, {}
        t1 = serve(llm1, prompts, new, r1)
        t4 = serve(llm4, prompts, new, r4)
        agree = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b),
                      len(x)) for x, y in zip(t4, t1)]
        # teacher-forced: each prompt followed by the tokens both streams
        # share (all but the last where they never part)
        ctx = [np.concatenate([p, x[:min(a, new - 1)]])
               for p, x, a in zip(prompts, t1, agree)]
        ref, _ = prefill_logits(llm1, llm1.engine, ctx)
        got, _ = prefill_logits(llm4, llm4.engine, ctx)
        compare_logits(rec, "prefill_logits", got, ref)
        parts = {i: parting(ref[i], got[i], t1[i][a], t4[i][a])
                 for i, a in enumerate(agree) if a < new}
        rec.update({"tp1": r1, "tp4": r4, "leading_tokens_equal": agree,
                    "required": AGREE_TOKENS, "partings": parts})
        check(all(a >= AGREE_TOKENS or parts[i]["near_tie"]
                  for i, a in enumerate(agree)),
              f"TP=4 and TP=1 greedy streams part early, not at a "
              f"near-tie: {agree} {parts}")
    del llm1

    with Phase("tp4_spd_quant8", devices) as rec:
        spd = load(4, args.seed, params=llm4.canonical, spd=0.5,
                   comm="quant8")
        serve(spd, prompts, new, rec)
        rec["dropped_syncs"] = spd.plan.n_dropped

    with Phase("decode_collectives", devices) as rec:
        rec["exact"] = decode_collectives(llm4)
        rec["spd0.5_quant8"] = decode_collectives(spd)
        n_exact = rec["exact"]["all-reduce"]["executed"]
        n_spd = rec["spd0.5_quant8"]["all-reduce"]["executed"]
        check(n_spd < n_exact,
              f"SPD decode executes {n_spd} all-reduces, exact {n_exact}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's first device is on "
                 f"platform {d0.platform!r} ({d0.device_kind})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPUs, JAX has {len(devices)}")
    devices = devices[:args.chips]

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    entries = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
               else 0)
    emit({"phase": "start", "arch": ARCH, "chips": args.chips,
          "seed": args.seed, "compile_cache": cache_dir,
          "cache_entries_at_start": entries})
    Phase.install()

    (four_chips if args.chips == 4 else one_chip)(args, devices)

    emit({"phase": "end", "cache_warm": entries > 0 and Phase.hits > 0,
          "cache_hits": Phase.hits, "cache_misses": Phase.misses})
    emit({"ok": True, "device": {"platform": d0.platform,
                                 "kind": d0.device_kind,
                                 "count": len(jax.devices())}})


if __name__ == "__main__":
    main()
