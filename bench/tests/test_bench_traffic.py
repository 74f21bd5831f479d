"""The traffic generator and the harness's discovery of cells by name."""
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench.lib import spec  # noqa: E402
from bench.lib import traffic as T  # noqa: E402

# each mix with the configuration it is sized for
MIXES = {"chat-open": "qwen3-1.7b", "chat-closed": "opt-6.7b-tp4-spd50"}
BIG_SEED = 2 ** 31 + 12345


def _mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def _config_for(mix):
    with open(os.path.join(ROOT, "bench", "configs",
                           f"{MIXES[mix]}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
def test_one_seed_gives_the_same_requests(mix):
    m = _mix(mix)
    cl = _config_for(mix)["load"]["cache_len"]
    a = T.generate(m, BIG_SEED, 45, 50272, cl)
    b = T.generate(m, BIG_SEED, 45, 50272, cl)
    c = T.generate(m, BIG_SEED + 1, 45, 50272, cl)
    assert [(r.max_new, r.due) for r in a] == [(r.max_new, r.due) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, c))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_sends_the_same_work(mix):
    m = _mix(mix)
    cl = _config_for(mix)["load"]["cache_len"]
    a = T.generate(m, 1, 45, 1000, cl)
    b = T.generate(m, 2, 45, 1000, cl)
    key = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs)
    assert key(a) == key(b)
    if m["loop"] == "open":
        gaps = lambda rs: sorted(np.round(np.diff([r.due for r in rs]), 9))
        assert sorted(r.due for r in a)[0] == -m["ramp_s"]
        assert len(a) == len(b)
        assert np.isclose(sum(gaps(a)), sum(gaps(b)), rtol=0.1)
    else:
        k = m["set_size"]
        for blk in range(len(a) // k):
            assert key(a[blk * k:(blk + 1) * k]) == key(a[:k])


def test_open_loop_sends_the_same_lengths_at_the_same_times_per_seed():
    m = _mix("chat-open")
    cl = _config_for("chat-open")["load"]["cache_len"]
    a = T.generate(m, BIG_SEED, 50, 1000, cl)
    b = T.generate(m, 3, 50, 1000, cl)
    k = m["block"]
    n = len(a)
    assert n % k == 0
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in b]
    # every prompt quantile is sent once, and each block of k arrivals
    # holds one prompt of each of the k strata
    pl = list(T.lognormal_quantiles(n, m["prompt_len"]))
    assert sorted(len(r.prompt) for r in a) == pl
    for i in range(0, n, k):
        strata = sorted(pl.index(len(r.prompt)) * k // n
                        for r in a[i:i + k])
        assert strata == list(range(k))


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_respect_clips_and_positions(mix):
    m = _mix(mix)
    cfg = _config_for(mix)
    load = cfg["load"]
    limit = min(load["cache_len"], cfg["max_position_embeddings"])
    reqs = T.generate(m, BIG_SEED, 45, 1000, load["cache_len"])
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= m["prompt_len"]["min"]
    assert p.max() <= m["prompt_len"]["max"]
    assert o.min() >= 1 and o.max() <= m["output_len"]["max"]
    assert (p + o).max() <= limit
    if mix == "chat-closed":
        assert (p + o).max() <= 2048


def test_lognormal_quantiles_median():
    q = T.lognormal_quantiles(101, {"median": 384, "sigma": 1.0,
                                    "min": 1, "max": 10 ** 6})
    assert q[50] == 384
    assert np.all(np.diff(q) >= 0)


def _copy_bench(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return str(root)


def test_new_cell_traffic_and_metric_are_found_without_edits(tmp_path):
    root = _copy_bench(tmp_path)
    before = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    # a new traffic mix, a new cell on an existing configuration, a new
    # per-layer metric: new files plus entries in BENCHMARK.json
    mix = dict(_mix("chat-open"), rate_per_s=1.0)
    with open(os.path.join(root, "bench", "traffic", "burst-x.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "bench", "checks",
                           "qwen3-1.7b.burst-x.json"), "w") as f:
        json.dump({"sample_tokens": 64, "max_requests": 2,
                   "limits": {"max_logit_gap": 1.0, "min_requests": 1}}, f)
    with open(os.path.join(root, "bench", "metrics", "sched.steps.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["workloads"].append({"name": "qwen3-1.7b.burst-x",
                            "config": "qwen3-1.7b", "traffic": "burst-x",
                            "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "sched.steps", "unit": "count",
                            "better": "higher", "source": "program_counter",
                            "layer": "scheduler", "moves": "out_tok_s",
                            "workloads": ["qwen3-1.7b.burst-x"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)

    cell = spec.cell("qwen3-1.7b.burst-x", root)
    assert cell.traffic["rate_per_s"] == 1.0
    assert cell.config["num_hidden_layers"] == 28
    assert "sched.steps" in [m["name"] for m in cell.per_layer]
    old = spec.cell("qwen3-1.7b.chat-open", root)
    assert "sched.steps" not in [m["name"] for m in old.per_layer]
    got = spec.read_metrics([m for m in cell.per_layer
                             if m["name"] == "sched.steps"], [1, 2, 3], root)
    assert got == {"sched.steps": {"value": 3.0, "unit": "count"}}
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, f"{path} was edited"


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="unknown workload"):
        spec.cell("no-such-cell")


def test_reader_that_finds_nothing_leaves_its_metric_out(tmp_path):
    root = _copy_bench(tmp_path)
    with open(os.path.join(root, "bench", "metrics", "x.none.py"),
              "w") as f:
        f.write("def read(run):\n    return None\n")
    assert spec.read_metrics([{"name": "x.none", "unit": "%"}], None,
                             root) == {}
