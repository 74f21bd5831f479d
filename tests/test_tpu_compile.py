"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached, at qwen3-1.7b widths in bf16 (d_head 128, 16 query / 8 KV
heads at TP=1, 4 / 2 at TP=4, d_model 2048).  The TPU compiler refuses
what interpret mode accepts — block shapes off the (8, 128) tiling,
kernels that overrun the scoped VMEM — so these tests catch it without a
chip.  Nothing runs: a pass means the chip's compiler accepted the
kernel, not that its results or speed are right.  One whole paged
decode step is compiled too, to check from its HLO and memory analysis
that the page pools are updated in place.

The topology is described inside a fixture, never at import time: only
one process may load the TPU compiler library, and every test worker
imports every test file."""
import os

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import SPDPlanConfig, replace
from repro.configs import get_config
from repro.core import model as M
from repro.kernels import ops
from repro.kernels import quant_collectives as QC
from repro.parallel import tp as TP
from repro.parallel.backend import ShardMapBackend
from repro.runtime import forward as F

D_MODEL, D_HEAD, PAGE = 2048, 128, 16
HEADS = {"tp1": (16, 8), "tp4": (4, 2)}        # (query, kv) heads per chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_prefill_2048(one_chip):
    hq, hkv = HEADS["tp1"]
    bf = jnp.bfloat16
    txt = _compiled_text(ops.flash_attention, one_chip,
                         ((1, 2048, hq, D_HEAD), bf),
                         ((1, 2048, hkv, D_HEAD), bf),
                         ((1, 2048, hkv, D_HEAD), bf))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("tp", ["tp1", "tp4"])
@pytest.mark.parametrize("chunk", [1, 256], ids=["decode", "prefill"])
def test_paged_flash_attention(one_chip, tp, chunk):
    """Decode (C=1) and one 256-token prefill chunk over a 1024-token
    table (64 pages) for 8 slots."""
    hq, hkv = HEADS[tp]
    slots, width, pages = 8, 64, 512
    bf = jnp.bfloat16
    txt = _compiled_text(ops.paged_attention, one_chip,
                         ((slots, chunk, hq, D_HEAD), bf),
                         ((pages + 1, PAGE, hkv, D_HEAD), bf),
                         ((pages + 1, PAGE, hkv, D_HEAD), bf),
                         ((slots, width), jnp.int32),
                         ((slots,), jnp.int32))
    assert "tpu_custom_call" in txt


# one block sync of a 256-token prefill chunk: the (256, d_model) partial
# each of the 4 chips all-reduces; the ring's receive side accumulates
# one quarter of it
SYNC = 256 * D_MODEL


@pytest.mark.parametrize("name", ["qdq_absmax", "quantize_absmax"])
def test_quant_kernels_block_sync(one_chip, name):
    txt = _compiled_text(getattr(QC, name), one_chip,
                         ((SYNC,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_dequant_accum_block_sync(one_chip):
    n = SYNC // 4
    txt = _compiled_text(QC.dequant_accum_absmax, one_chip,
                         ((n,), jnp.int8), ((n // 128,), jnp.float32),
                         ((n,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_paged_decode_updates_pools_in_place(topo):
    """The served paged decode step (runtime/forward.paged_decode_step on
    the shard backend) at qwen3-1.7b widths, cut to 4 layers, 512 pages
    of 16, 8 slots and a 64-page table: the layer scan carries the
    stacked page pools, so no op copies or dynamic-update-slices a whole
    pool, no op slices out one layer's pool, and the step's temporaries
    stay below one pool's bytes."""
    from jax.sharding import Mesh
    cfg = replace(get_config("qwen3-1.7b"), n_layers=4)
    plan = SPDPlanConfig.none(cfg.n_layers)
    slots, width, pages = 8, 64, 512
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    step = ShardMapBackend(cfg, plan, mesh).wrap(
        *F.paged_decode_step(cfg, plan, tp=1))

    def shapes(tree, pspecs):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, TP.named(mesh, pspecs))

    params = shapes(jax.eval_shape(lambda: M.stack_segments(
        M.pad_model(M.init_model(jax.random.PRNGKey(0), cfg), cfg, 1),
        cfg, plan)), TP.param_pspecs(cfg, plan))
    pools = shapes(M.paged_cache_struct(
        cfg, plan, slots, width * PAGE, 1, page_size=PAGE, num_pages=pages),
        TP.cache_pspecs(cfg, plan, mesh, shard_batch=False))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    compiled = step.lower(
        params,
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((slots, width), jnp.int32, sharding=rep),
        pools).compile()
    pool = pools[0]["k"]
    assert pool.shape == (cfg.n_layers, pages + 1, PAGE, 8, D_HEAD)
    shape = "bf16[" + ",".join(map(str, pool.shape)) + "]"
    txt = compiled.as_text()
    pool_sized = [ln.strip() for ln in txt.splitlines()
                  if re.search(r"= " + re.escape(shape)
                               + r"\S* (copy|dynamic-update-slice)\(", ln)]
    assert not pool_sized, pool_sized
    # nor is one layer's pool sliced out of the stack to be read
    layer = r"= bf16\[(1,)?" + ",".join(map(str, pool.shape[1:])) + r"\]"
    layer_sized = [ln.strip() for ln in txt.splitlines()
                   if re.search(layer, ln)]
    assert not layer_sized, layer_sized
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
