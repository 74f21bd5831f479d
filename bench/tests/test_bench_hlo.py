"""The benchmark's copy of `collective_counts` on the program's own
compiled decode step: at TP=4 over four virtual CPU devices and a
reduced OPT shape, the SPD plan executes exactly one all-reduce fewer
per dropped block than the exact plan."""
import os
import sys

import jax
import numpy as np
import pytest

try:       # the tier-1 conftest asks for the same 8 devices
    jax.config.update("jax_num_cpu_devices", 8)
except RuntimeError:
    pass

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib.hlo import collective_counts  # noqa: E402

LAYERS = 4


def _decode_allreduces(spd: float) -> int:
    import jax.numpy as jnp
    from repro.api import LLM
    from repro.config.base import ModelConfig

    cfg = ModelConfig(name="opt-small", family="dense", n_layers=LAYERS,
                      d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                      vocab_size=256, qkv_bias=True, o_bias=True,
                      mlp_bias=True, gated_mlp=False, act="relu",
                      norm="layernorm", pos_emb="learned", max_seq_len=64,
                      rope_fraction=0.0, tie_embeddings=True,
                      dtype="float32")
    llm = LLM.load(cfg, engine="shard", tp=4, spd=spd, page_size=8,
                   num_pages=16, prefill_chunk=16, cache_len=64,
                   max_batch=4)
    s = llm.serve()
    step = llm.engine._decode_paged(False)
    txt = step.lower(llm.params, jnp.asarray(s.cur), jnp.asarray(s.pos),
                     jnp.full((4, 8), -1, jnp.int32),
                     s.kv.pcaches).compile().as_text()
    return collective_counts(txt)["all-reduce"]["executed"]


def test_spd_drops_one_allreduce_per_dropped_block():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    exact = _decode_allreduces(0.0)
    spd = _decode_allreduces(0.5)
    assert exact - spd == LAYERS // 2
    # two syncs per block plus the embedding and the greedy pmax/pmin
    assert exact == 2 * LAYERS + 3


def test_counts_loop_trips_and_dtypes():
    hlo = "\n".join([
        "%body (p: (s32[], bf16[8])) -> (s32[], bf16[8]) {",
        "  %ar = bf16[8]{0} all-reduce(bf16[8]{0} %x), to_apply=%add",
        "}",
        "%cond (p: (s32[], bf16[8])) -> pred[] {",
        "  %n = s32[] constant(5)",
        "  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT",
        "}",
        "ENTRY %main (a: bf16[8]) -> bf16[8] {",
        "  %w = (s32[], bf16[8]) while((s32[], bf16[8]) %t), "
        "condition=%cond, body=%body",
        "  %ar2 = f32[4]{0} all-reduce(f32[4]{0} %y), to_apply=%add",
        "}"])
    c = collective_counts(hlo)["all-reduce"]
    assert c["sites"] == 2
    assert c["executed"] == 6
    assert c["dtypes"] == {"bf16": 5, "f32": 1}
    assert np.all([collective_counts(hlo)[op]["executed"] == 0
                   for op in ("all-gather", "reduce-scatter")])
