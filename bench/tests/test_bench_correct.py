"""`correct` on a whole run at a size the CPU holds: the harness (with its
look for a chip skipped) serves a small model through the program and
judges it against the float32 reference.  A sound run is correct; a run
with a fault planted in the timed path underneath is not, once for each
fault a served cell can have; and the fp8 control reads far wider gaps
than the program."""
import argparse
import os
import sys

import jax
import pytest

try:       # the tier-1 conftest asks for the same 8 devices
    jax.config.update("jax_num_cpu_devices", 8)
except RuntimeError:
    pass

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib import spec  # noqa: E402

LIMIT = 1e-3      # logits; sound float32 runs here read 0.0
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

QWEN = {"model_type": "qwen3", "attention_bias": False, "head_dim": 16,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
        "max_position_embeddings": 4096, "num_attention_heads": 4,
        "num_hidden_layers": 2, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "tie_word_embeddings": True, "torch_dtype": "float32",
        "vocab_size": 512,
        "load": {"tp": 1, "spd": 0.0, "page_size": 8, "num_pages": 64,
                 "prefill_chunk": 16, "cache_len": 128, "max_batch": 4}}
OPT = {"model_type": "opt", "activation_function": "relu",
       "do_layer_norm_before": True, "ffn_dim": 128, "hidden_size": 64,
       "max_position_embeddings": 128, "num_attention_heads": 4,
       "num_hidden_layers": 4, "torch_dtype": "float32", "vocab_size": 512,
       "word_embed_proj_dim": 64,
       "load": {"tp": 4, "spd": 0.5, "page_size": 8, "num_pages": 64,
                "prefill_chunk": 16, "cache_len": 128, "max_batch": 4}}
# every slot busy all the time and 16 finished requests compared, so a
# fault in any row shows
MIX = {"loop": "closed", "clients": 4, "set_size": 8, "pool": 512,
       "prompt_len": {"median": 24, "sigma": 0.8, "min": 8, "max": 64},
       "output_len": {"median": 10, "sigma": 0.5, "min": 4, "max": 16},
       "ramp_s": 0.3, "trace_s": 0.3, "prefix_cache": False}


@pytest.fixture(scope="module")
def runmod():
    return spec.load_module(os.path.join(ROOT, "bench", "run.py"),
                            "bench_run_for_tests")


def _run(runmod, monkeypatch, config, seed=7, control=False, trace=0):
    monkeypatch.setattr(runmod, "compile_cache", lambda: "off")
    bm = spec.benchmark()
    cell = spec.Cell(
        name="small", chips=config["load"]["tp"], config_name="small",
        config=config, traffic_name="small", traffic=MIX,
        check={"sample_tokens": 10 ** 6, "max_requests": 16,
               "limits": {"max_logit_gap": LIMIT, "min_requests": 2}},
        end_to_end=bm["end_to_end"], per_layer=bm["per_layer"])
    args = argparse.Namespace(workload="small", seed=seed, seconds=1.5,
                              trace=trace)
    devs = jax.devices()[:cell.chips]
    return runmod.run_one(cell, args, devs, PEAKS, len(jax.devices()),
                          control=control)


def _patch_decode(monkeypatch, fn):
    """Wrap the program's paged decode step: fn(args, (tokens, caches))."""
    from repro.runtime.engines import Engine

    orig = Engine.decode_paged

    def wrapped(self, params, tokens, pos, table, pcaches):
        return fn((self, params, tokens, pos, table, pcaches),
                  orig(self, params, tokens, pos, table, pcaches))

    monkeypatch.setattr(Engine, "decode_paged", wrapped)


@pytest.mark.parametrize("config", [QWEN, OPT], ids=["tp1", "tp4_spd"])
def test_sound_run_is_correct(runmod, monkeypatch, config):
    out = _run(runmod, monkeypatch, config)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "ttft_p90_ms",
                                   "itl_p50_ms", "itl_p99_ms",
                                   "out_tok_s", "setup_s"}
    assert list(out)[-1] == "check"


def test_traced_run_reads_the_program_spans(runmod, monkeypatch, capsys):
    """A --trace 1 run attaches the program's Recorder for the window
    alone: the admission and host-phase readers find its spans there,
    and the scheduler is left with no recorder attached."""
    import json

    from bench.lib import harness as H
    from repro.obs import NULL_RECORDER

    seen, free = [], H.Server.free

    def spy(self):
        seen.append(self.sched.obs)
        free(self)

    monkeypatch.setattr(H.Server, "free", spy)
    out = _run(runmod, monkeypatch, QWEN, trace=1)
    assert out["correct"], out["check"]
    m = out["metrics"]
    assert m["sched.admit_ms"]["value"] > 0
    assert m["sched.host_ms"]["value"] > 0
    assert seen == [NULL_RECORDER]
    window = next(json.loads(ln) for ln in
                  capsys.readouterr().out.splitlines()
                  if '"phase": "window"' in ln)
    gaps = window["token_gaps"]
    assert gaps["n"] > 0 and 0 < gaps["admit_share"] < 1
    assert gaps["p50"] <= gaps["p99"] <= gaps["p99.5"]


def _token_altered(a, res):
    nxt, pc = res
    return (nxt + 1) % 512, pc


def _state_unchanged(a, res):
    import jax.numpy as jnp
    # the cache the step was given, not the one it wrote (copied before
    # the call would donate it, so rebuild it as blank pages)
    nxt, pc = res
    return nxt, jax.tree.map(jnp.zeros_like, pc)


def _half_batch(a, res):
    import jax.numpy as jnp
    _, _, tokens, _, _, _ = a
    nxt, pc = res
    half = nxt.shape[0] // 2
    return jnp.concatenate([nxt[:half], tokens[half:]]), pc


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_fault_in_the_timed_path_is_not_correct(runmod, monkeypatch, fault):
    _patch_decode(monkeypatch, fault)
    out = _run(runmod, monkeypatch, QWEN)
    assert not out["correct"], out["check"]


def test_exchange_between_chips_left_out_is_not_correct(runmod,
                                                        monkeypatch):
    from repro.parallel import collectives

    monkeypatch.setattr(collectives, "g_psum", lambda x, axis: x)
    out = _run(runmod, monkeypatch, OPT)
    assert not out["correct"], out["check"]


def test_fp8_control_reads_wider_gaps(runmod, monkeypatch, capsys):
    import json

    out = _run(runmod, monkeypatch, QWEN, control=True)
    rec = next(json.loads(ln) for ln in capsys.readouterr().out.splitlines()
               if '"control"' in ln)
    assert out["correct"]
    assert rec["program_check"] == out["check"]
    assert rec["correct"] is False, rec["check"]
    assert rec["check"]["max_logit_gap"]["value"] > LIMIT


def test_sample_holds_the_longest_and_min_requests():
    import numpy as np

    from bench.lib import check as C
    from bench.lib import traffic as T

    def req(rid, p, o):
        r = T.Request(rid=rid, prompt=np.zeros(p, np.int32), max_new=o)
        r.handle = argparse.Namespace(out=[0] * o)
        return r

    done = [req(0, 1536, 512), req(1, 100, 20), req(2, 300, 40)]
    got = C.sample(done, 2 ** 31 + 7, 512, 8, 2)
    assert got[0].rid == 0 and len(got) == 2
    assert len(C.sample(done, 5, 10 ** 6, 8, 2)) == 3
