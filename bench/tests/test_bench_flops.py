"""The benchmark's FLOP and byte functions and its table of peaks."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib import flops as F  # noqa: E402
from bench.lib import spec  # noqa: E402
from bench.lib.peaks import peaks  # noqa: E402

CONFIGS = ["qwen3-1.7b", "opt-6.7b-tp4-spd50"]


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def _model(cfg):
    return spec.load_module(
        os.path.join(ROOT, "bench", "models", f"{cfg['model_type']}.py"),
        f"bench_model_{cfg['model_type']}")


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_program(name):
    from repro.config.base import ModelConfig

    cfg = _config(name)
    m = _model(cfg)
    program = ModelConfig(name=name, **m.program_config(cfg))
    assert F.param_count(m.arch(cfg)) == program.param_count()


@pytest.mark.parametrize("name", CONFIGS)
def test_decode_bytes_follow_real_contexts_not_the_table(name):
    cfg = _config(name)
    a = _model(cfg).arch(cfg)
    tp = cfg["load"]["tp"]
    short = F.decode_bytes_per_chip(a, tp, [100] * 8)
    long = F.decode_bytes_per_chip(a, tp, [1000] * 8)
    kv = F.kv_bytes_per_token(a) / tp
    # 900 more positions in each of 8 rows: exactly their keys and values
    assert long - short == pytest.approx(8 * 900 * kv)
    # the bytes name no table width: a batch whose widest row would
    # bucket to a wider table needs only its own rows' bytes
    mixed = F.decode_bytes_per_chip(a, tp, [100] * 7 + [1000])
    assert mixed - short == pytest.approx(900 * kv)


def test_decode_flops_count_rows_and_contexts():
    a = _model(_config("qwen3-1.7b")).arch(_config("qwen3-1.7b"))
    one = F.decode_flops(a, [0])
    assert F.decode_flops(a, [0, 0]) == pytest.approx(2 * one)
    attn = 4.0 * a.heads * a.head_dim * a.layers
    assert F.decode_flops(a, [10]) - one == pytest.approx(10 * attn)


def test_prefill_flops_is_causal():
    a = _model(_config("qwen3-1.7b")).arch(_config("qwen3-1.7b"))
    attn = 4.0 * a.heads * a.head_dim * a.layers
    lin = 2.0 * a.layers * F.layer_matmul_params(a)
    head = 2.0 * a.vocab * a.d
    assert F.prefill_flops(a, 3) == pytest.approx(3 * lin + head + 6 * attn)


def test_peaks_known_kind_and_unknown_kind_raises():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
