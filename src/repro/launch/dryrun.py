import os

if __name__ == "__main__":  # importing this module must not resize jax's device set
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell on the production mesh with ShapeDtypeStruct inputs (no allocation),
record ``memory_analysis()`` / ``cost_analysis()`` / collective-operand
bytes parsed from the compiled HLO — the §Dry-run and §Roofline evidence.

When run as a script, the flag above MUST precede any other import (jax locks the device
count at first init).  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # full sweep
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Results land in results/dryrun/<arch>__<shape>__<mesh>.json (incremental:
existing cells are skipped unless --force).
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.shapes import SHAPES, applicable_shapes
from repro.distributed.sharding import (
    ShardingPolicy,
    batch_shardings,
    logits_sharding,
    make_cache_shardings,
    make_opt_shardings,
    make_param_shardings,
)
from repro.launch.mesh import make_production_mesh
from repro.models.registry import ALL_ARCHS, get_model
from repro.optim import adamw
from repro.train.train_step import make_train_step

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16|c64|c128)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-operand bytes of every collective op in the compiled HLO.

    HLO lines look like ``%all-reduce.3 = f32[16,1024]{1,0} all-reduce(...``
    (or a tuple of shapes).  We take the result type(s) on the lhs of the
    op name occurrence."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for c in _COLLECTIVES:
            marker = f" {c}("
            if marker in stripped and not stripped.startswith("//"):
                lhs = stripped.split(marker)[0]
                # result types appear after '=' and before the op name
                if "=" in lhs:
                    lhs = lhs.split("=", 1)[1]
                nbytes = 0
                for dt, dims in _SHAPE_RE.findall(lhs):
                    n = 1
                    for d in dims.split(","):
                        if d:
                            n *= int(d)
                    nbytes += n * _DTYPE_BYTES.get(dt, 4)
                out[c] += nbytes
                counts[c] += 1
                break
    return {"bytes": out, "counts": counts, "total_bytes": sum(out.values())}


def build_cell(arch: str, shape: str, mesh, policy: ShardingPolicy,
               *, microbatches: int = 1):
    """Returns (jitted_fn, arg_specs) for one (arch, shape) cell."""
    api = get_model(arch)
    cfg = api.config
    suite = SHAPES[shape]

    param_specs = api.param_specs(cfg)
    p_shard = make_param_shardings(mesh, cfg, param_specs, policy)

    if suite.kind == "train":
        opt_cfg = adamw.AdamWConfig()
        opt_specs = jax.eval_shape(lambda p: adamw.init(opt_cfg, p), param_specs)
        o_shard = make_opt_shardings(mesh, cfg, opt_specs, p_shard, policy)
        batch_specs = api.batch_specs(cfg, suite)
        b_shard = batch_shardings(mesh, cfg, batch_specs, policy)
        step = make_train_step(api, cfg, opt_cfg, remat=True, microbatches=microbatches)
        fn = jax.jit(
            step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        )
        return fn, (param_specs, opt_specs, batch_specs)

    if suite.kind == "prefill":
        cache_specs = api.cache_specs(cfg, suite)
        c_shard = make_cache_shardings(mesh, cfg, cache_specs, policy)
        batch_specs = api.batch_specs(cfg, suite)
        b_shard = batch_shardings(mesh, cfg, batch_specs, policy)
        lg_shard = logits_sharding(mesh, cfg, suite.global_batch, policy)
        extras = {k: v for k, v in batch_specs.items() if k != "tokens"}

        def prefill_fn(params, tokens, cache, extra):
            return api.module.prefill(params, cfg, tokens, cache, **extra)

        fn = jax.jit(
            prefill_fn,
            in_shardings=(p_shard, b_shard["tokens"], c_shard, batch_shardings(mesh, cfg, extras, policy)),
            out_shardings=(lg_shard, c_shard),
            donate_argnums=(2,),
        )
        return fn, (param_specs, batch_specs["tokens"], cache_specs, extras)

    if suite.kind == "decode":
        cache_specs = api.cache_specs(cfg, suite)
        c_shard = make_cache_shardings(mesh, cfg, cache_specs, policy)
        tok_spec = api.batch_specs(cfg, suite)["token"]
        t_shard = batch_shardings(mesh, cfg, {"token": tok_spec}, policy)["token"]
        lg_shard = logits_sharding(mesh, cfg, suite.global_batch, policy)

        def decode_fn(params, token, cache):
            return api.module.decode_step(params, cfg, token, cache)

        fn = jax.jit(
            decode_fn,
            in_shardings=(p_shard, t_shard, c_shard),
            out_shardings=(lg_shard, c_shard),
            donate_argnums=(2,),
        )
        return fn, (param_specs, tok_spec, cache_specs)

    raise ValueError(suite.kind)


POLICIES: dict[str, ShardingPolicy] = {
    # baseline: FSDP params over data, TP over model, batch over (pod,)data
    "baseline": ShardingPolicy(dp_axes=("data",), tp_axes=("model",)),
    # pure data parallel: params FSDP over both axes, no TP (small models)
    "no-tp": ShardingPolicy(dp_axes=("data", "model"), tp_axes=()),
    # serve-oriented: params TP-only (no per-layer FSDP weight all-gather)
    "serve-tp": ShardingPolicy(dp_axes=("data",), tp_axes=("model",),
                               param_fsdp_axes=()),
    # serve, fully-sharded weights over both axes (256-way TP)
    "serve-tp2": ShardingPolicy(dp_axes=("data",), tp_axes=("data", "model"),
                                param_fsdp_axes=()),
    # sequence-parallel residual stream (train)
    "seqpar": ShardingPolicy(dp_axes=("data",), tp_axes=("model",),
                             sequence_parallel=True),
    # FSDP across pods too (params over DCN)
    "fsdp-pod": ShardingPolicy(dp_axes=("data",), tp_axes=("model",),
                               fsdp_over_pod=True),
    # sequence parallel + TP-only params (no FSDP weight gathers)
    "seqpar-tp": ShardingPolicy(dp_axes=("data",), tp_axes=("model",),
                                sequence_parallel=True, param_fsdp_axes=()),
    # sequence parallel + explicit EP sharding of the MoE dispatch buffer
    "seqpar-ep": ShardingPolicy(dp_axes=("data",), tp_axes=("model",),
                                sequence_parallel=True),
}


def run_cell(arch: str, shape: str, mesh_kind: str, *, force: bool = False,
             policy: ShardingPolicy | None = None, tag: str = "",
             microbatches: int = 1) -> dict:
    name = f"{arch}__{shape}__{mesh_kind}{tag}"
    out_path = RESULTS / f"{name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    if policy is None:
        policy = POLICIES["baseline"]
    record: dict = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "tag": tag,
        "mesh_shape": dict(mesh.shape), "status": "unknown",
    }
    try:
        from jax.sharding import PartitionSpec as P

        from repro.distributed import hints

        act_spec = None
        if policy.sequence_parallel:
            from jax.sharding import NamedSharding

            dp = tuple(a for a in ("pod",) + policy.dp_axes if a in mesh.axis_names)
            spec = P(dp if len(dp) > 1 else dp[0],
                     policy.tp_axes if len(policy.tp_axes) > 1
                     else (policy.tp_axes[0] if policy.tp_axes else None),
                     None)
            act_spec = NamedSharding(mesh, spec)  # carries the mesh — no
            # context-mesh requirement at trace time
        moe_spec = None
        if tag.startswith("@seqpar-ep"):
            from jax.sharding import NamedSharding

            # dispatch-aware: experts over model (EP), capacity over data —
            # keeps the token scatter aligned with the batch/seq shards
            moe_spec = NamedSharding(mesh, P("model", "data", None))
        with hints.activation_pspec(act_spec), hints.moe_buffer_pspec(moe_spec):
            # hints are consulted at trace time → keep them active through
            # lower()
            fn, specs = build_cell(arch, shape, mesh, policy,
                                   microbatches=microbatches)
            lowered = fn.lower(*specs)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
        coll = collective_bytes(txt)
        # trip-count-aware HLO costs (cost_analysis counts while bodies once)
        from repro.launch.hlo_costs import analyze_hlo_text

        hlo = analyze_hlo_text(txt).to_json()

        api = get_model(arch)
        cfg = api.config
        suite = SHAPES[shape]
        if suite.kind == "train":
            tokens = suite.global_batch * suite.seq_len
            model_flops = 6 * cfg.active_param_count() * tokens
        elif suite.kind == "prefill":
            tokens = suite.global_batch * suite.seq_len
            model_flops = 2 * cfg.active_param_count() * tokens
        else:
            tokens = suite.global_batch
            model_flops = 2 * cfg.active_param_count() * tokens

        record.update(
            status="ok",
            lower_s=round(t_lower - t0, 2),
            compile_s=round(t_compile - t_lower, 2),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
            },
            cost={
                "flops_per_device": ca.get("flops", 0.0),
                "bytes_accessed_per_device": ca.get("bytes accessed", 0.0),
            },
            hlo_costs=hlo,
            collectives=coll,
            model_flops_total=model_flops,
            tokens=tokens,
            params_total=cfg.param_count(),
            params_active=cfg.active_param_count(),
        )
    except Exception as e:  # record failures — they are bugs to fix
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    RESULTS.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(record, indent=2))
    flops = record.get("cost", {}).get("flops_per_device", 0)
    print(f"[{record['status']:5s}] {name}  compile={record.get('compile_s', '-')}s "
          f"flops/dev={flops:.3e}" if record["status"] == "ok"
          else f"[{record['status']:5s}] {name}  {record.get('error', '')[:200]}",
          flush=True)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--policy", choices=list(POLICIES), default="baseline",
                    help="sharding-policy preset (§Perf hillclimbing)")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in ALL_ARCHS:
            for shape in applicable_shapes(arch):
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    tag = "" if args.policy == "baseline" else f"@{args.policy}"
    if args.microbatches > 1:
        tag += f"@mb{args.microbatches}"
    failures = 0
    for mesh_kind in meshes:
        for arch, shape in cells:
            rec = run_cell(arch, shape, mesh_kind, force=args.force,
                           policy=POLICIES[args.policy], tag=tag,
                           microbatches=args.microbatches)
            failures += rec["status"] != "ok"
    print(f"done: {len(cells) * len(meshes)} cells, {failures} failures", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
