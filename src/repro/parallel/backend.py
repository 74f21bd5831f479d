"""`ParallelBackend` — how a per-shard forward step becomes a program.

The serving stack used to carry two parallel implementations of every
forward step: `SimEngine` hand-vmapped each step over a leading
``(tp, ...)`` axis while `ShardEngine` routed through per-step
`shard_map` builders in `parallel/tp.py`.  This module collapses the
difference to ONE seam: a backend wraps a *backend-agnostic local
function* (written as if running on a single model shard, using named
collectives over `MODEL_AXIS`) into a jitted step, and owns the three
layout decisions that go with it —

  * how params/caches are *placed* (leading vmap axis vs NamedSharding),
  * how a blank cache tree is materialized in that placement,
  * which argument positions are donated (KV caches on decode/verify).

Step builders live in `repro.runtime.forward`; each returns a
``(local_fn, StepSpec)`` pair and `backend.wrap` does the rest.  The
registry at the bottom is what `repro.api.LLM.load(engine=...)` and the
parity-test sweep resolve names through: registering a third backend
(e.g. a multi-replica DP or overlapped-collective variant) makes it
load-able and parity-tested with zero changes elsewhere.

See docs/architecture.md for the full design and an add-a-backend
walkthrough.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from repro.parallel.collectives import MODEL_AXIS

# argument / result kinds a StepSpec can declare:
#   "params"        the stacked parameter tree (model-sharded placement)
#   "cache"         a KV-cache tree in the step's cache layout
#   "batch"         a per-request array (sharded over DP axes when the
#                   spec says shard_batch, replicated otherwise)
#   "rep"           a replicated scalar/array (positions, page tables)
#   "logits_shard"  vocab-parallel logits left UN-gathered, one slice
#                   per model shard (dry-run lowering/analysis only)
KINDS = ("params", "cache", "batch", "rep", "logits_shard")


@dataclass(frozen=True)
class StepSpec:
    """Layout contract of one forward step.

    in_kinds / out_kinds   one KIND per positional argument / result
    donate                 argument indices whose buffers the step may
                           reuse in place (KV caches on decode/verify)
    shard_batch            whether "batch"-kind args and cache batch
                           axes shard over the DP axes (dense decode)
                           or stay replicated (paged / chunked steps,
                           where any slot may touch any page)
    """

    in_kinds: Tuple[str, ...]
    out_kinds: Tuple[str, ...]
    donate: Tuple[int, ...] = ()
    shard_batch: bool = True

    def __post_init__(self):
        for k in self.in_kinds + self.out_kinds:
            if k not in KINDS:
                raise ValueError(f"unknown step-arg kind {k!r}")


class ParallelBackend:
    """Protocol base.  A backend binds (cfg, plan) to a parallel
    execution strategy; the unified `repro.runtime.engines.Engine`
    drives everything through this surface:

        wrap(local_fn, spec) -> jitted step
        place_params(stacked) -> params in native placement
        blank_caches(structs, shard_batch=) -> blank cache trees
        tp / dp / dp_total / cache_batch_axis  topology + layout facts
    """

    #: registry key; subclasses set it (also used in BENCH json configs)
    name: str = "?"

    #: whether this backend schedules kept syncs to overlap with block
    #: compute; `LatencyModel.summarize(ledger, overlap=...)` reads it
    #: to price a trace's hidden vs exposed comm time (bench_transfer)
    overlaps_comm: bool = False

    cfg = plan = None
    tp: int = 1
    dp: int = 1
    #: index of the batch axis in this backend's cache leaves
    #: (sim split form carries a leading (tp, ...) axis, so batch sits
    #: one deeper than the shard-local (layer, batch, ...) view)
    cache_batch_axis: int = 1

    @classmethod
    def build(cls, cfg, plan, *, tp: int = 1, dp: int = 1,
              mesh=None) -> "ParallelBackend":
        raise NotImplementedError

    @property
    def dp_total(self) -> int:
        """Rows a batch must pad to a multiple of (1 = no constraint)."""
        return 1

    def wrap(self, local_fn, spec: StepSpec):
        raise NotImplementedError

    def place_params(self, stacked: dict):
        raise NotImplementedError

    def blank_caches(self, structs, *, shard_batch: bool = True):
        raise NotImplementedError


def _named_as(fn, local_fn):
    """`fn` under `local_fn`'s name: `jax.jit` names the compiled module
    after the function it is given, and a wrapper keeps the step's."""
    fn.__name__ = fn.__qualname__ = local_fn.__name__
    return fn


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_BACKENDS: Dict[str, Type[ParallelBackend]] = {}


def register_backend(name: str):
    """Class decorator: `@register_backend("sim")` makes the backend
    resolvable by `LLM.load(engine="sim")` and sweeps it into every
    registry-parametrized parity test (tests/, scripts/backend_parity)."""
    def deco(cls):
        cls.name = name
        _BACKENDS[name] = cls
        return cls
    return deco


def backend_names() -> Tuple[str, ...]:
    return tuple(_BACKENDS)


def resolve_backend(name: str) -> Type[ParallelBackend]:
    if name not in _BACKENDS:
        raise ValueError(f"unknown engine {name!r} "
                         f"(registered backends: {backend_names()})")
    return _BACKENDS[name]


def resolved_backend_name(name: str) -> str:
    """'sim' -> 'sim/VmapSimBackend' — the fully resolved identity the
    BENCH_<name>.json config blocks record."""
    return f"{name}/{resolve_backend(name).__name__}"


def make_backend(name: str, cfg, plan, *, tp: int = 1, dp: int = 1,
                 mesh=None) -> ParallelBackend:
    return resolve_backend(name).build(cfg, plan, tp=tp, dp=dp, mesh=mesh)


# ---------------------------------------------------------------------------
# vmap simulated TP (1 CPU device)
# ---------------------------------------------------------------------------


@register_backend("sim")
class VmapSimBackend(ParallelBackend):
    """Simulated TP: the model axis is a vmap axis over a leading
    ``(tp, ...)`` dimension on every param/cache leaf (core/simtp.py
    owns the split/merge math).  `lax.psum`/`all_gather` over the
    vmapped axis name execute EXACTLY the distributed math on one
    device, so algorithm work and tests run without a mesh."""

    cache_batch_axis = 2          # leaves are (tp, layer, batch, ...)

    def __init__(self, cfg, plan, tp: int):
        self.cfg, self.plan, self.tp, self.dp = cfg, plan, tp, 1

    @classmethod
    def build(cls, cfg, plan, *, tp=1, dp=1, mesh=None):
        if dp != 1:
            raise ValueError("engine='sim' simulates TP on one device; "
                             f"dp must be 1 (got {dp})")
        return cls(cfg, plan, tp)

    def wrap(self, local_fn, spec: StepSpec):
        in_axes = tuple(0 if k in ("params", "cache") else None
                        for k in spec.in_kinds)
        vf = jax.vmap(local_fn, in_axes=in_axes, axis_name=MODEL_AXIS)

        def fn(*args):
            outs = vf(*args)
            # cache / logits_shard outputs keep the stacked per-shard
            # axis (that IS the split layout); replicated outputs take
            # shard 0's copy
            return tuple(o if k in ("cache", "logits_shard")
                         else jax.tree.map(lambda x: x[0], o)
                         for o, k in zip(outs, spec.out_kinds))

        return jax.jit(_named_as(fn, local_fn), donate_argnums=spec.donate)

    def place_params(self, stacked: dict):
        """The split tree on JAX's default device, wherever the stacked
        tree was built (the host, for `repro.api.LLM`)."""
        from repro.core import simtp
        return jax.device_put(
            simtp.split_stacked(stacked, self.cfg, self.plan, self.tp),
            jax.devices()[0])

    def blank_caches(self, structs, *, shard_batch: bool = True):
        from repro.core import model as M
        from repro.parallel.layout import REPLICATED
        ints = M.cache_specs_tree(self.cfg, self.plan)

        def one(s, a):
            if a == REPLICATED:
                return jnp.zeros((self.tp,) + s.shape, s.dtype)
            shp = list(s.shape)
            shp[a] //= self.tp
            return jnp.zeros((self.tp,) + tuple(shp), s.dtype)

        return [jax.tree.map(one, s, i) for s, i in zip(structs, ints)]


# ---------------------------------------------------------------------------
# shard_map over a real device mesh (the production path)
# ---------------------------------------------------------------------------


@register_backend("shard")
class ShardMapBackend(ParallelBackend):
    """Real TP: every step runs under one `shard_map` over the mesh,
    Megatron-style explicit collectives over the "model" axis and DP
    over "data"/"pod" (parallel/tp.py holds the pspec builders and the
    train step; parallel/collectives.py explains grad-inside-map)."""

    cache_batch_axis = 1          # leaves are (layer, batch, ...)

    def __init__(self, cfg, plan, mesh):
        self.cfg, self.plan, self.mesh = cfg, plan, mesh
        self.tp = mesh.shape[MODEL_AXIS]
        dp = 1
        for a in mesh.axis_names:
            if a != MODEL_AXIS:
                dp *= mesh.shape[a]
        self.dp = dp

    @classmethod
    def build(cls, cfg, plan, *, tp=1, dp=1, mesh=None):
        if mesh is None:
            from repro.launch.mesh import make_test_mesh
            mesh = make_test_mesh(dp, tp)
        return cls(cfg, plan, mesh)

    @property
    def dp_total(self) -> int:
        return self.dp

    def _kind_specs(self, spec: StepSpec):
        from jax.sharding import PartitionSpec as P
        from repro.parallel import tp as TP
        dpx = TP.dp_axes(self.mesh) if spec.shard_batch else ()
        return {
            "params": TP.param_pspecs(self.cfg, self.plan),
            "cache": TP.cache_pspecs(self.cfg, self.plan, self.mesh,
                                     shard_batch=spec.shard_batch),
            "batch": P(dpx),
            "rep": P(),
            "logits_shard": P(dpx, MODEL_AXIS),
        }

    def wrap(self, local_fn, spec: StepSpec):
        from repro.parallel import tp as TP
        kinds = self._kind_specs(spec)
        return jax.jit(TP.shard_map(
            local_fn, self.mesh,
            in_specs=tuple(kinds[k] for k in spec.in_kinds),
            out_specs=tuple(kinds[k] for k in spec.out_kinds)),
            donate_argnums=spec.donate)

    def place_params(self, stacked: dict):
        """Each device receives only its own shard of every leaf: the
        stacked tree stays wherever it was built (the host, for
        `repro.api.LLM`), never whole on one chip."""
        from repro.parallel import tp as TP
        return jax.device_put(stacked, TP.named(
            self.mesh, TP.param_pspecs(self.cfg, self.plan)))

    def blank_caches(self, structs, *, shard_batch: bool = True):
        from repro.parallel import tp as TP
        sh = TP.named(self.mesh, TP.cache_pspecs(
            self.cfg, self.plan, self.mesh, shard_batch=shard_batch))
        return [jax.tree.map(
            lambda s, h: jnp.zeros(s.shape, s.dtype, device=h), st, shh)
            for st, shh in zip(structs, sh)]


# ---------------------------------------------------------------------------
# shard_map with overlapped kept syncs
# ---------------------------------------------------------------------------


@register_backend("overlap")
class OverlapBackend(ShardMapBackend):
    """`shard` plus a comm schedule that HIDES the syncs SPD keeps.

    Three seams, same math (greedy outputs bit-identical to `shard`,
    locked by the registry parity sweeps):

      * every step traces inside `collectives.overlap_region`, so each
        kept quantized sync logs its two hops as `ring_chunks` ring-step
        collective-permute entries instead of one RS/AG pair — the
        chunked decomposition that double-buffers against the same
        block's MLP on a real interconnect (the runnable ppermute rings
        live in compression.ring_*; the CPU emulation keeps the single
        psum so numerics match `shard` exactly);
      * `overlaps_comm=True` tells `LatencyModel.summarize` to price
        overlappable entries as hidden-behind-compute, which is how
        bench_transfer attributes hidden vs exposed time per policy;
      * the Engine's `decode_pipelined` driver async-dispatches
        independent decode micro-batches back-to-back, overlapping
        launch/host work of batch t+1 with device execution of batch t.

    docs/comm.md#overlap walks through the model and its knobs."""

    overlaps_comm = True
    #: ring-pipeline depth of each kept sync (matches
    #: LatencyModel.ring_chunks so the ledger and the price agree)
    ring_chunks: int = 4

    def wrap(self, local_fn, spec: StepSpec):
        from repro.parallel.collectives import overlap_region

        def overlapped(*args):
            with overlap_region(self.ring_chunks):
                return local_fn(*args)

        return super().wrap(_named_as(overlapped, local_fn), spec)
