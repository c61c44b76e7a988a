"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production mesh, print memory/cost analysis, and derive the
roofline terms.

This file MUST set XLA_FLAGS before any other import (jax locks the
device count on first init): 512 placeholder host devices cover the
2-pod production mesh; the single-pod 16x16 mesh uses the first 256.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
  PYTHONPATH=src python -m repro.launch.dryrun --all --json out.json
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

KV_QUANT = os.environ.get("REPRO_KV_QUANT", "0") == "1"
TRAIN_PLAN_ENV = os.environ.get("REPRO_TRAIN_PLAN", "")  # "" | "fsdp"

from repro.configs import ASSIGNED, get_config, input_specs
from repro.nn import runtime
from repro.launch.mesh import HW, make_production_mesh
from repro.launch.roofline import (
    Roofline, model_flops, parse_collectives,
)
from repro.launch.steps import (
    make_decode_step, make_prefill_step, make_train_step,
)
from repro.models.lm import LM
from repro.nn.config import SHAPES, ModelConfig, ShapeCell
from repro.nn.param import struct_tree
from repro.nn.sharding import ShardingConfig, param_pspec
from repro.train.optim import AdamWConfig, state_specs

PAPER_HEADS = {"yi-34b": 56, "qwen2-vl-7b": 28}

# Per-arch training memory plan: (microbatches, optimizer-state dtype,
# grad-accumulation dtype). μ trades activation memory for ×μ FSDP
# all-gathers; bf16 states halve optimizer memory at the 100B+ scale —
# both choices are reported in the §Roofline table per cell.
TRAIN_PLAN = {
    "default": (4, "float32", jnp.float32),
    "jamba-1.5-large-398b": (8, "bfloat16", jnp.bfloat16),
    "qwen1.5-110b": (8, "bfloat16", jnp.bfloat16),
    "deepseek-v2-236b": (8, "bfloat16", jnp.bfloat16),
    "deepseek-v3-671b": (8, "bfloat16", jnp.bfloat16),
    "yi-34b": (8, "float32", jnp.float32),
    "xlstm-350m": (1, "float32", jnp.float32),
    "seamless-m4t-large-v2": (1, "float32", jnp.float32),
}


def train_plan(arch: str):
    mb, sdt, accum = TRAIN_PLAN.get(arch, TRAIN_PLAN["default"])
    return AdamWConfig(state_dtype=sdt), mb, accum


def skip_reason(cfg: ModelConfig, cell: ShapeCell) -> str | None:
    if cell.name == "long_500k" and not cfg.sub_quadratic:
        return "full-attention arch: long_500k skipped (DESIGN.md §4)"
    return None


def _structs(mesh, spec_tree, shard_cfg: ShardingConfig | None = None):
    shard_cfg = shard_cfg or ShardingConfig()
    resolve = lambda s: param_pspec(mesh, s, shard_cfg)
    return struct_tree(spec_tree, mesh, resolve)


def serve_shard_cfg(cfg: ModelConfig, mesh) -> ShardingConfig:
    """Serving parallelism plan (§Perf iteration 1): ZeRO-style param
    sharding over the data axis is a *training* memory optimization — at
    serve time it turns every step into a full-weight all-gather. When the
    TP-sharded weights fit HBM (≤8 GiB/chip budget), disable FSDP so
    weights replicate across data (zero per-step weight traffic); only the
    100B+ models keep FSDP at serve time."""
    from repro.launch.roofline import active_params

    _, total = active_params(cfg)
    tp = mesh.shape.get("model", 1)
    per_dev = total * 2 / tp  # bf16
    return ShardingConfig(enable_fsdp=per_dev > 8 * 2**30)


def _with_repeat(cfg: ModelConfig, n: int) -> ModelConfig:
    """Depth-n variant of a config (for metric extrapolation). The model is
    affine in n: metric(N) = metric(1) + (N-1)·[metric(2) - metric(1)]."""
    return dataclasses.replace(
        cfg,
        n_repeat=n,
        enc_repeat=n if cfg.enc_repeat else 0,
    )


def build_lowerable(arch: str, shape: str, mesh, cfg: ModelConfig = None,
                    force_mb1: bool = False, force_mb: int | None = None):
    """Returns (fn, args_structs, donate) ready for jit().lower()."""
    cfg = cfg or get_config(arch)
    cell = SHAPES[shape]
    lm = LM(cfg)
    batch = input_specs(cfg, cell, mesh)

    if cell.kind == "train":
        opt_cfg, mb, accum = train_plan(arch)
        shard_cfg = None
        if TRAIN_PLAN_ENV == "fsdp":
            shard_cfg = ShardingConfig.fsdp_only()
            mb = 1  # batch shards over all chips; no accumulation needed
        elif TRAIN_PLAN_ENV == "fsdp_hybrid":
            shard_cfg = ShardingConfig.fsdp_hybrid()
        if force_mb is not None:
            mb = force_mb
        elif force_mb1:
            mb = 1
        pspecs = lm.param_specs()
        params = _structs(mesh, pspecs, shard_cfg)
        opt = _structs(mesh, state_specs(opt_cfg, pspecs), shard_cfg)
        step = make_train_step(
            cfg, mesh, opt_cfg, remat="full", microbatches=mb,
            accum_dtype=accum, shard_cfg=shard_cfg,
        )
        return step, (params, opt, batch), (0, 1)
    scfg = serve_shard_cfg(cfg, mesh)
    if cell.kind == "prefill":
        params = _structs(mesh, lm.param_specs(), scfg)
        step = make_prefill_step(cfg, mesh)
        return step, (params, batch), ()
    # decode
    params = _structs(mesh, lm.param_specs(), scfg)
    caches = _structs(
        mesh,
        lm.cache_specs(
            cell.global_batch, cell.seq_len,
            enc_len=cell.seq_len if cfg.enc_dec else 0,
            kv_quant=KV_QUANT,
        ),
        scfg,
    )
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    step = make_decode_step(cfg, mesh)
    return step, (params, batch["tokens"], caches, pos), (2,)


def _compile_metrics(arch: str, shape: str, mesh, cfg, mb=None) -> dict:
    """flops / bytes / wire of one compile (per device)."""
    fn, args, donate = build_lowerable(
        arch, shape, mesh, cfg=cfg,
        force_mb1=mb is None, force_mb=mb,
    )
    with mesh:
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text(), mesh.size)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "wire": colls.wire_bytes,
        "by_op": colls.by_op,
        "counts": colls.counts,
    }


def _affine(key, lo, hi, steps):
    return lo[key] + steps * (hi[key] - lo[key])


def extrapolated_metrics(arch: str, shape: str, mesh) -> dict:
    """Exact per-device metrics via (depth × microbatch) extrapolation.

    XLA's cost analysis counts a while-loop body once, so rolled compiles
    undercount scanned superblocks; and collectives are NOT simply ×μ
    (XLA hoists loop-invariant weight gathers out of the grad-accum scan —
    measured, see EXPERIMENTS §Perf iteration 0). We therefore compile
    fully-unrolled variants at (n, μ) ∈ {1,2}² and extrapolate bilinearly:
       m(N, M) = m11 + (N−1)Δn + (M−1)Δμ + (N−1)(M−1)Δnμ
    (non-train cells have no μ axis; plain depth extrapolation applies;
    the sLSTM time scan stays rolled — its per-step FLOPs are negligible).
    """
    cfg = get_config(arch)
    cell = SHAPES[shape]
    n = cfg.n_repeat
    mu = train_plan(arch)[1] if cell.kind == "train" else 1
    if TRAIN_PLAN_ENV == "fsdp":
        mu = 1  # fsdp-only plan shards batch over all chips; no accumulation
    elif TRAIN_PLAN_ENV == "fsdp_hybrid":
        mu = train_plan(arch)[1]
    runtime.UNROLL = 1_000_000
    try:
        m11 = _compile_metrics(arch, shape, mesh, _with_repeat(cfg, 1), mb=1)
        m21 = (
            _compile_metrics(arch, shape, mesh, _with_repeat(cfg, 2), mb=1)
            if n > 1 else m11
        )
        if mu > 1:
            m12 = _compile_metrics(
                arch, shape, mesh, _with_repeat(cfg, 1), mb=2
            )
            m22 = (
                _compile_metrics(
                    arch, shape, mesh, _with_repeat(cfg, 2), mb=2
                ) if n > 1 else m12
            )
        else:
            m12, m22 = m11, m21
    finally:
        runtime.UNROLL = 1

    def bilinear(get):
        a = get(m11)
        dn = get(m21) - a
        dm = get(m12) - a
        dnm = get(m22) - get(m21) - get(m12) + a
        return a + (n - 1) * dn + (mu - 1) * dm + (n - 1) * (mu - 1) * dnm

    out = {}
    for key in ("flops", "bytes", "wire"):
        out[key] = bilinear(lambda m, k=key: m[k])
    ops = set().union(*[m["by_op"] for m in (m11, m21, m12, m22)])
    out["by_op"] = {
        o: bilinear(lambda m, o=o: m["by_op"].get(o, 0.0)) for o in ops
    }
    cts = set().union(*[m["counts"] for m in (m11, m21, m12, m22)])
    out["counts"] = {
        o: int(bilinear(lambda m, o=o: m["counts"].get(o, 0))) for o in cts
    }
    return out


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True,
             metrics: bool = True):
    cfg = get_config(arch)
    cell = SHAPES[shape]
    reason = skip_reason(cfg, cell)
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
    }
    if reason:
        rec["status"] = "SKIP"
        rec["reason"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    fn, args, donate = build_lowerable(arch, shape, mesh)
    with mesh:
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = parse_collectives(hlo, n_dev)

    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    mf = model_flops(
        cfg, cell.kind, tokens, paper_heads=PAPER_HEADS.get(arch)
    )
    if metrics:
        mx = extrapolated_metrics(arch, shape, mesh)
    else:
        mx = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "wire": colls.wire_bytes,
            "by_op": colls.by_op,
            "counts": colls.counts,
        }
    rl = Roofline(
        flops=mx["flops"],
        bytes_accessed=mx["bytes"],
        wire_bytes=mx["wire"],
        n_devices=n_dev,
        model_flops=mf,
    )
    hbm = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )
    rec.update(
        status="OK",
        t_lower_s=round(t_lower, 1),
        t_compile_s=round(t_compile, 1),
        arg_bytes=ma.argument_size_in_bytes,
        out_bytes=ma.output_size_in_bytes,
        temp_bytes=ma.temp_size_in_bytes,
        alias_bytes=ma.alias_size_in_bytes,
        hbm_per_device=hbm,
        fits_hbm=bool(hbm <= HW["hbm_bytes"]),
        flops_per_device=rl.flops,
        bytes_per_device=rl.bytes_accessed,
        wire_bytes_per_device=rl.wire_bytes,
        raw_flops_rolled=float(ca.get("flops", 0.0)),
        coll_by_op={k: round(v) for k, v in mx["by_op"].items()},
        coll_counts=mx["counts"],
        t_compute=rl.t_compute,
        t_memory=rl.t_memory,
        t_collective=rl.t_collective,
        bottleneck=rl.bottleneck,
        model_flops=mf,
        useful_flops_ratio=rl.useful_flops_ratio,
        mfu=rl.mfu,
    )
    if verbose:
        print(f"--- {arch} × {shape} × {rec['mesh']} ---")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(
            f"  memory/device: args {ma.argument_size_in_bytes/2**30:.2f}GiB "
            f"out {ma.output_size_in_bytes/2**30:.2f}GiB "
            f"temp {ma.temp_size_in_bytes/2**30:.2f}GiB "
            f"alias {ma.alias_size_in_bytes/2**30:.2f}GiB "
            f"-> {hbm/2**30:.2f}GiB "
            f"({'fits' if rec['fits_hbm'] else 'EXCEEDS'} 16GiB HBM)"
        )
        print(
            f"  per-device: {rl.flops/1e12:.2f} TFLOP, "
            f"{rl.bytes_accessed/2**30:.2f} GiB accessed, "
            f"{rl.wire_bytes/2**20:.1f} MiB on wire {mx['counts']}"
        )
        print(
            f"  roofline: compute {rl.t_compute*1e3:.2f}ms "
            f"memory {rl.t_memory*1e3:.2f}ms "
            f"collective {rl.t_collective*1e3:.2f}ms "
            f"-> bottleneck={rl.bottleneck} "
            f"useful={rl.useful_flops_ratio:.2f} mfu={rl.mfu:.3f}"
        )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--json", default=None, help="append JSONL records here")
    args = ap.parse_args()

    cells: list[tuple[str, str, bool]] = []
    if args.all:
        for arch in ASSIGNED:
            for shape in SHAPES:
                cells.append((arch, shape, False))
                if not args.single_pod_only:
                    cells.append((arch, shape, True))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required without --all")
        cells.append((args.arch, args.shape, args.multi_pod))

    records = []
    failed = []
    for arch, shape, mp in cells:
        try:
            # roofline metrics are a single-pod deliverable; the multi-pod
            # pass proves the pod axis shards (compile + memory only)
            rec = run_cell(arch, shape, mp, metrics=not mp)
        except Exception as e:  # noqa: BLE001 — report all cell failures
            traceback.print_exc()
            rec = {
                "arch": arch, "shape": shape,
                "mesh": "2x16x16" if mp else "16x16",
                "status": "FAIL", "error": f"{type(e).__name__}: {e}",
            }
            failed.append(rec)
        records.append(rec)
        if args.json:
            with open(args.json, "a") as f:
                f.write(json.dumps(rec) + "\n")

    ok = sum(1 for r in records if r["status"] == "OK")
    skip = sum(1 for r in records if r["status"] == "SKIP")
    print(f"\n=== dry-run: {ok} OK, {skip} SKIP, {len(failed)} FAIL ===")
    if failed:
        for r in failed:
            print(f"  FAIL {r['arch']} × {r['shape']} × {r['mesh']}: {r['error']}")
        sys.exit(1)


if __name__ == "__main__":
    main()
