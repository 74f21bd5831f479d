"""End-to-end CLI smoke tests (subprocess), dry-run single cells, and the
Pallas attention backend integrated into the full model."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_cfg
from repro.config.base import SPDPlanConfig, replace
from repro.core import model as M, simtp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))


def _run(args, timeout=420):
    # fresh process => fresh XLA device-count env for the CLIs
    env = dict(ENV)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-m"] + args, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout,
                          env=env)


def test_train_cli_fsdp(tmp_path):
    r = _run(["repro.launch.train", "--arch", "smollm-360m-reduced",
              "--steps", "8", "--tp", "2", "--dp", "2", "--fsdp",
              "--ckpt-dir", str(tmp_path), "--batch", "4", "--seq", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["final_step"] == 8
    assert np.isfinite(out["final_loss"])


def test_serve_cli_shard_engine():
    r = _run(["repro.launch.serve", "--arch", "smollm-360m-reduced",
              "--tp", "2", "--dp", "2", "--requests", "3",
              "--max-new", "4", "--engine", "shard"])
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["completed"] == 3
    assert all(len(v) >= 4 for v in out["outputs"].values())


@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """$JAX_COMPILATION_CACHE_DIR when set (JAX reads it; nothing else
    is set), else the fixed `.jax_cache/` at the repository root."""
    from repro.launch import compile_cache

    set_dirs = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_dirs.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert set_dirs == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert set_dirs == []


def test_test_mesh_needs_enough_devices():
    from repro.launch.mesh import make_test_mesh
    n = len(jax.devices())
    with pytest.raises(RuntimeError, match=f"needs {2 * n} devices"):
        make_test_mesh(2, n)
    assert make_test_mesh(1, n).devices.shape == (1, n)


@pytest.mark.parametrize("cell", [
    ("smollm-360m", "decode_32k", "single", "0.0"),
    ("hymba-1.5b", "long_500k", "multi", "0.7"),
])
def test_dryrun_single_cell(cell, tmp_path):
    """One real 512-device dry-run cell per family class (own process:
    the placeholder device count locks at first jax init)."""
    arch, shape, mesh, spd = cell
    out = str(tmp_path / "cell.json")
    r = _run(["repro.launch.dryrun", "--arch", arch, "--shape", shape,
              "--mesh", mesh, "--spd", spd, "--json", out], timeout=560)
    assert r.returncode == 0, r.stderr[-2000:]
    with open(out) as f:
        rec = json.load(f)
    assert rec["applicable"]
    assert rec["flops_total"] > 0
    assert sum(rec["hlo_collective_op_counts"].values()) > 0
    assert any(v > 0 for v in rec["ledger_bytes_per_device"].values())


def test_pallas_backend_full_model_parity():
    """attn_backend="pallas" routes prefill/train attention through the
    flash kernel (interpret mode on CPU) — logits must match XLA path."""
    cfg_x = make_cfg("smollm-360m")
    cfg_p = replace(cfg_x, attn_backend="pallas")
    params = M.init_model(jax.random.PRNGKey(0), cfg_x)
    plan = SPDPlanConfig.first_k(cfg_x.n_layers, 2)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg_x.vocab_size, (2, 128)))
    lx = simtp.make_logits_fn(cfg_x, plan, 2, q_chunk=64)(
        simtp.prepare_params(params, cfg_x, plan, 2), toks, None)
    lp = simtp.make_logits_fn(cfg_p, plan, 2, q_chunk=64)(
        simtp.prepare_params(params, cfg_p, plan, 2), toks, None)
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp), atol=2e-4)
