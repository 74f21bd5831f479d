import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (arch × shape × mesh) cell.

THE proof that the distribution config is coherent without hardware:
``jax.jit(step).lower(**input_specs).compile()`` must succeed on the
production 16×16 pod mesh AND the 2×16×16 multi-pod mesh for all 40
(arch × shape) cells; the compiled artifact yields memory_analysis
(fits-per-device) and cost_analysis (FLOPs/bytes) for §Roofline, and the
trace-time collective ledger yields exact per-step logical collective
bytes (the HLO text count is also recorded — but ops inside lax.scan
bodies execute L times, which text counting cannot see; the ledger can).

One cell per process invocation (device count locks at first jax init);
`--all` orchestrates subprocesses in parallel.

Usage:
    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k \
        --mesh single --spd 0.7 --json out.json
    python -m repro.launch.dryrun --all --out-dir results/dryrun -j 8
"""
import argparse
import json
import sys


HW = {  # TPU v5e-ish targets used across §Roofline
    "peak_flops_bf16": 197e12,
    "hbm_gbps": 819e9,
    "ici_link_gbps": 50e9,
    "dcn_gbps": 1.5e9,   # per-chip cross-pod share
    "hbm_bytes": 16e9,
}

LONG_CTX_OK = {"mamba2-370m", "hymba-1.5b"}   # sub-quadratic only


def cell_applicable(arch: str, shape: str) -> bool:
    if shape == "long_500k" and arch not in LONG_CTX_OK:
        return False      # quadratic-attention wall; documented skip
    return True


def spd_plan_for(cfg, fraction: float, comm: str = "exact",
                 comm_logits: str = "exact"):
    from repro.config.base import CommPolicy, SPDPlanConfig
    if not cfg.spd_applicable or fraction <= 0:
        plan = SPDPlanConfig.none(cfg.n_layers)
    else:
        k = int(round(cfg.n_layers * fraction))
        plan = SPDPlanConfig.first_k(cfg.n_layers, k)
    if comm != "exact" or comm_logits != "exact":
        plan = plan.with_comm(CommPolicy.uniform(cfg.n_layers, comm,
                                                 logits=comm_logits))
    return plan


def input_structs(cfg, shape_cfg, plan, tp):
    import jax
    import jax.numpy as jnp
    from repro.core import model as M

    gb, s = shape_cfg.global_batch, shape_cfg.seq_len
    if shape_cfg.kind == "train":
        toks = s - (cfg.frontend_len if cfg.frontend_dim else 0)
        batch = {
            "tokens": jax.ShapeDtypeStruct((gb, toks), jnp.int32),
            "labels": jax.ShapeDtypeStruct((gb, toks), jnp.int32),
            "mask": jax.ShapeDtypeStruct((gb, toks), jnp.float32),
        }
        if cfg.frontend_dim:
            batch["embeds"] = jax.ShapeDtypeStruct(
                (gb, cfg.frontend_len, cfg.frontend_dim), jnp.dtype(cfg.dtype))
        return batch
    if shape_cfg.kind == "prefill":
        toks = s - (cfg.frontend_len if cfg.frontend_dim else 0)
        out = {"tokens": jax.ShapeDtypeStruct((gb, toks), jnp.int32)}
        if cfg.frontend_dim:
            out["embeds"] = jax.ShapeDtypeStruct(
                (gb, cfg.frontend_len, cfg.frontend_dim), jnp.dtype(cfg.dtype))
        return out
    # decode: one new token against a seq_len cache
    return {
        "tokens": jax.ShapeDtypeStruct((gb, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((gb,), jnp.int32),
        "caches": M.cache_struct(cfg, plan, gb, s, tp),
    }


def param_structs(cfg, plan, tp):
    import jax
    from repro.core import model as M

    def build():
        key = jax.random.PRNGKey(0)
        canonical = M.init_model(key, cfg)
        return M.stack_segments(M.pad_model(canonical, cfg, tp), cfg, plan)

    return jax.eval_shape(build)


def _collective_hlo_counts(txt: str):
    """Collective CALL SITES in compiled HLO; ops inside while bodies
    execute once per trip — the ledger is the byte-exact accounting,
    this is the structural cross-check (parallel/hlo.py also counts
    executions)."""
    from repro.parallel.hlo import collective_counts
    return {op: c["sites"] for op, c in collective_counts(txt).items()}


def bytes_per_device(total, mesh_axes_in_spec):
    return total


def run_cell(arch, shape_name, mesh_kind, spd,
             out_json=None, verbose=True, sync_q8=False, kv_int8=False,
             w_int8=False, comm="exact", comm_logits="exact"):
    import contextlib
    import jax
    import numpy as np
    from repro.config.base import SHAPES, replace
    from repro.configs import get_config
    from repro.core import model as M
    from repro.launch.mesh import make_production_mesh
    from repro.parallel import tp as TP
    from repro.parallel.collectives import collective_ledger, sync_compression

    cfg = get_config(arch)
    if kv_int8:
        cfg = replace(cfg, kv_dtype="int8")
    if w_int8:
        cfg = replace(cfg, weight_dtype="int8")
    shape_cfg = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    tp = mesh.shape["model"]
    n_dev = int(np.prod(list(mesh.shape.values())))
    dp_total = n_dev // tp
    # an explicit CommPolicy rides the plan (per-block, serve paths);
    # the legacy --sync-q8 context stays as the blanket trace override
    plan = spd_plan_for(cfg, spd, comm, comm_logits)

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "spd": spd, "n_devices": n_dev, "tp": tp,
           "sync_q8": sync_q8, "kv_int8": kv_int8, "w_int8": w_int8,
           "comm": comm, "comm_logits": comm_logits,
           "applicable": cell_applicable(arch, shape_name)}
    if not rec["applicable"]:
        rec["skip_reason"] = ("full-attention arch at 524k dense KV: the "
                              "quadratic wall this shape exposes; see "
                              "DESIGN.md §Arch-applicability")
        _emit(rec, out_json, verbose)
        return rec

    pstructs = param_structs(cfg, plan, tp)
    ins = input_structs(cfg, shape_cfg, plan, tp)
    shard_batch = shape_cfg.global_batch % dp_total == 0

    q8ctx = (sync_compression(sync_q8 if isinstance(sync_q8, str) else "int8")
             if sync_q8 else contextlib.nullcontext())
    with q8ctx, collective_ledger() as ledger:
        if shape_cfg.kind == "train":
            mbs = max(1, shape_cfg.global_batch // dp_total)  # micro size 1
            ts = TP.TrainStepConfig(microbatches=mbs, remat=True,
                                    q_chunk=min(2048, shape_cfg.seq_len),
                                    fsdp=True)
            step, init, specs = TP.build_train_step(
                cfg, plan, mesh, ts, stacked_shapes=pstructs)
            opt_structs = jax.eval_shape(init, pstructs)
            lowered = step.lower(pstructs, opt_structs, ins)
        elif shape_cfg.kind == "prefill":
            # the shared step table lifted by the registered shard
            # backend; logits stay vocab-sharded (gather_logits=False)
            # so the per-cell ledger measures the model's own syncs,
            # not the serve-path logits gather
            from repro.parallel.backend import make_backend
            from repro.runtime import forward as F
            backend = make_backend("shard", cfg, plan, mesh=mesh)
            pre = backend.wrap(*F.prefill_step(
                cfg, plan, tp=tp, q_chunk=min(1024, shape_cfg.seq_len),
                cache_len=0, gather_logits=False,
                shard_batch=shard_batch))
            lowered = pre.lower(pstructs, ins["tokens"], None,
                                ins["embeds"] if cfg.frontend_dim
                                else None)
        else:
            # the production decode: the shared step table lifted by the
            # registered shard backend (exactly what serving compiles)
            from repro.parallel.backend import make_backend
            from repro.runtime import forward as F
            backend = make_backend("shard", cfg, plan, mesh=mesh)
            dec = backend.wrap(*F.decode_step(cfg, plan, tp=tp,
                                              shard_batch=shard_batch))
            lowered = dec.lower(pstructs, ins["tokens"], ins["pos"],
                                ins["caches"])

    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):        # JAX 0.4.x: one dict per
        cost = cost[0] if cost else {}         # partition; newer: a dict
    hlo = compiled.as_text()

    led = {}
    for e in ledger:
        key = f"{e.op}@{e.axis}"
        led[key] = led.get(key, 0) + e.nbytes

    rec.update({
        "flops_total": float(cost.get("flops", 0.0)),
        "bytes_accessed_total": float(cost.get("bytes accessed", 0.0)),
        # memory_analysis values are PER-PARTITION (per device) already;
        # donated inputs (params/opt in train) appear under alias_size.
        "mem_per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "hlo_collective_op_counts": _collective_hlo_counts(hlo),
        "ledger_bytes_per_device": led,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "tokens": shape_cfg.tokens if shape_cfg.kind != "decode"
                  else shape_cfg.global_batch,
        "kind": shape_cfg.kind,
    })
    _emit(rec, out_json, verbose)
    return rec


def _emit(rec, out_json, verbose):
    if out_json:
        with open(out_json, "w") as f:
            json.dump(rec, f, indent=1)
    if verbose:
        if not rec.get("applicable", True):
            print(f"SKIP {rec['arch']} × {rec['shape']} × {rec['mesh']}: "
                  f"{rec['skip_reason']}")
            return
        m = rec["mem_per_device"]
        print(f"OK {rec['arch']} × {rec['shape']} × {rec['mesh']} "
              f"spd={rec['spd']}: flops={rec['flops_total']:.3e} "
              f"arg/dev={m['argument_bytes']/1e9:.2f}GB "
              f"temp/dev={m['temp_bytes']/1e9:.2f}GB "
              f"hlo_colls={rec['hlo_collective_op_counts']}")


# ---------------------------------------------------------------------------
# Orchestration (subprocess per cell: device count locks at first jax init)
# ---------------------------------------------------------------------------

def run_all(out_dir: str, jobs: int, archs=None, shapes=None, meshes=None,
            spds=(0.0, 0.7)):
    import itertools
    import subprocess
    from concurrent.futures import ThreadPoolExecutor

    from repro.config.base import SHAPES
    from repro.configs import ASSIGNED

    os.makedirs(out_dir, exist_ok=True)
    archs = archs or ASSIGNED
    shapes = shapes or list(SHAPES)
    meshes = meshes or ["single", "multi"]
    cells = list(itertools.product(archs, shapes, meshes, spds))

    def one(cell):
        arch, shape, mesh, spd = cell
        name = f"{arch}_{shape}_{mesh}_spd{int(spd*100)}"
        out = os.path.join(out_dir, name + ".json")
        if os.path.exists(out):
            print(f"cached {name}")
            return 0
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--mesh", mesh,
               "--spd", str(spd), "--json", out]
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=3600)
        if r.returncode != 0:
            with open(os.path.join(out_dir, name + ".err"), "w") as f:
                f.write(r.stdout + "\n" + r.stderr)
            print(f"FAIL {name}: see {name}.err (tail: "
                  f"{r.stderr.strip().splitlines()[-1] if r.stderr.strip() else '?'} )")
            return 1
        print(r.stdout.strip().splitlines()[-1] if r.stdout.strip() else name)
        return 0

    with ThreadPoolExecutor(max_workers=jobs) as ex:
        fails = sum(ex.map(one, cells))
    print(f"dry-run: {len(cells) - fails}/{len(cells)} cells green")
    return fails


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--spd", type=float, default=0.0)
    ap.add_argument("--sync-q8", action="store_true")
    ap.add_argument("--sync-q4", action="store_true")
    ap.add_argument("--comm", choices=["exact", "quant8", "quant4"],
                    default="exact",
                    help="CommPolicy level for kept sync points (per-plan "
                         "path; --sync-q8 is the legacy trace-time blanket)")
    ap.add_argument("--comm-logits", choices=["exact", "quant8", "quant4"],
                    default="exact")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--w-int8", action="store_true")
    ap.add_argument("--json")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("-j", "--jobs", type=int, default=4)
    ap.add_argument("--archs", nargs="*")
    ap.add_argument("--shapes", nargs="*")
    ap.add_argument("--meshes", nargs="*")
    args = ap.parse_args()
    if args.all:
        sys.exit(run_all(args.out_dir, args.jobs, args.archs, args.shapes,
                         args.meshes))
    run_cell(args.arch, args.shape, args.mesh, args.spd, args.json,
             sync_q8=("int4" if args.sync_q4 else args.sync_q8),
             kv_int8=args.kv_int8, w_int8=args.w_int8,
             comm=args.comm, comm_logits=args.comm_logits)


if __name__ == "__main__":
    main()
