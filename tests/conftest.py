"""Test fixtures.  8 CPU devices for real shard_map TP tests (set before
the backend initializes; smoke tests simply don't use the mesh).  The
512-device dry-run platform is NEVER set here — dryrun.py owns that in
its own subprocess."""
import jax

jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest

import jax.numpy as jnp
from repro.config.base import SPDPlanConfig, replace
from repro.configs import get_config
from repro.core import model as M


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(params=[2, 4, 8], ids=lambda t: f"tp{t}")
def tp_degree(request):
    """Shared TP-degree axis for engine/grad parity tests (the conftest
    pins 8 CPU devices, so shard_map meshes exist for every value; pair
    with `dp_for` to fill the remaining device budget)."""
    return request.param


def dp_for(tp: int, max_dev: int = 8) -> int:
    """Largest DP degree that fits beside `tp` on the 8 test devices."""
    return max(1, max_dev // tp)


def make_cfg(name, **kw):
    return replace(get_config(name, reduced=True), dtype="float32", **kw)


def engine_for_backend(name, cfg, plan, tp, *, params=None, q_chunk=64,
                       dp=None):
    """Unified `Engine` + placed params for one REGISTRY backend.

    The parity tests sweep `repro.parallel.backend.backend_names()`
    through this helper, so registering a new backend automatically
    enrolls it in the whole parity matrix.  `dp` defaults to the widest
    data parallelism the 8 test devices allow (backends that reject
    dp > 1, like "sim", fall back to dp=1)."""
    from repro.parallel.backend import make_backend
    from repro.runtime.engines import Engine

    canonical = (params if params is not None
                 else M.init_model(jax.random.PRNGKey(0), cfg))
    if dp is None:
        try:
            backend = make_backend(name, cfg, plan, tp=tp,
                                   dp=min(2, dp_for(tp)))
        except ValueError as e:
            # only the documented "this backend cannot do DP" rejection
            # falls back — any other build failure is a real bug
            if "dp must be 1" not in str(e):
                raise
            backend = make_backend(name, cfg, plan, tp=tp, dp=1)
    else:
        backend = make_backend(name, cfg, plan, tp=tp, dp=dp)
    eng = Engine(cfg, plan, backend, q_chunk=q_chunk)
    placed = backend.place_params(
        M.stack_segments(M.pad_model(canonical, cfg, tp), cfg, plan))
    return eng, placed


def make_batch(cfg, b=2, s=32, seed=0):
    r = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(r.integers(0, cfg.vocab_size, (b, s))),
             "labels": jnp.asarray(r.integers(0, cfg.vocab_size, (b, s))),
             "mask": jnp.ones((b, s), jnp.float32)}
    if cfg.frontend_dim:
        batch["embeds"] = jnp.asarray(
            r.standard_normal((b, cfg.frontend_len, cfg.frontend_dim)),
            jnp.float32)
    return batch


def leaves_allclose(a, b, atol=1e-5, rtol=1e-5):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=atol, rtol=rtol)
