import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
derive the roofline terms from the compiled artifact.

Two passes per cell:

  * **compile/memory pass** — the FULL config, layers under ``lax.scan``
    (unroll=1). Proves the sharding is coherent (SPMD partitioning succeeds),
    yields ``memory_analysis()`` (per-device bytes — proves it fits HBM).
  * **cost pass (secant)** — ``cost_analysis`` counts a scan body ONCE, not
    × trip-count, so per-layer cost is measured from two (three for hybrid)
    small fully-unrolled probe configs and extrapolated linearly in L:
    cost(L) = base + n_blocks(L)·per_block [+ n_rem·per_rem]. Exact because
    unrolled layers are cost-identical; validated against full unroll for
    whisper-tiny (4L) in tests/test_dryrun_probes.py.

Collective bytes are not in cost_analysis: we parse the partitioned HLO and
sum per-device wire bytes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (factors: AR=2×out, RS=1×in, AG/A2A/CP=1×out
— ring-algorithm estimates, documented in EXPERIMENTS.md).

CPU-backend caveat (recorded in every artifact): XLA CPU upcasts bf16
matmul operands to f32 (convert-before-gather), inflating HLO bytes and
collective bytes up to 2× vs the TPU lowering. FLOPs are unaffected.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""
import argparse
import dataclasses
import json
import re
import time
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding

from repro.axes import use_mesh
from repro.configs.base import ModelConfig, all_configs, get_config
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import (SHAPES, ShapeSpec, applicable,
                                 default_q_chunk, input_specs)
from repro.models import lm
from repro.optim.adamw import OptConfig, abstract_opt
from repro.runtime import steps as steps_mod

# --------------------------------------------------------------- HW constants
PEAK_FLOPS = 197e12        # TPU v5e bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link

_COLL_RE = re.compile(
    r"%?([\w.\-]+)\s*=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(([^)]*)\)"
)
_SHAPE_RE = re.compile(r"%?([\w.\-]+)\s*=\s*\(?([a-z0-9]+)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def _nbytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Per-device wire-byte estimate per collective kind (partitioned HLO)."""
    # name -> (dtype, dims) for operand-shape resolution (reduce-scatter)
    defs: Dict[str, Tuple[str, str]] = {}
    for m in _SHAPE_RE.finditer(hlo_text):
        defs[m.group(1)] = (m.group(2), m.group(3))

    out: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        name, dtype, dims, kind, operands = m.groups()
        obytes = _nbytes(dtype, dims)
        if kind == "all-reduce":
            wire = 2.0 * obytes
        elif kind == "reduce-scatter":
            wire = float(obytes)  # fallback: output bytes
            # operand may carry an inline shape, else resolve its name
            m_in = re.search(r"([a-z0-9]+)\[([\d,]*)\]", operands)
            if m_in:
                wire = float(_nbytes(m_in.group(1), m_in.group(2)))
            else:
                ops = [o.strip().split()[-1].lstrip("%")
                       for o in operands.split(",") if o.strip()]
                if ops and ops[0] in defs:
                    wire = float(_nbytes(*defs[ops[0]]))  # input ≈ ring wire
        else:  # all-gather / all-to-all / collective-permute
            wire = float(obytes)
        out[kind] = out.get(kind, 0.0) + wire
        count[kind] = count.get(kind, 0) + 1
    return {"bytes_by_kind": out, "count_by_kind": count,
            "total_bytes": sum(out.values())}


# ------------------------------------------------------------------ lowering
_abstract_opt = abstract_opt


def lower_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
               unroll: int = 1, q_chunk: Optional[int] = None,
               chunk_unroll: int = 1, fsdp: bool = True, remat: bool = True,
               n_micro: int = 1, kv_variant: str = "auto"):
    """Lower one (cfg, shape) on mesh. Returns jax ``Lowered``."""
    if q_chunk is None:
        q_chunk = default_q_chunk(cfg, shape)
    abstract_params = lm.abstract_params(cfg, max_seq=shape.seq_len)
    p_sh = shd.param_shardings(cfg, abstract_params, mesh, fsdp=fsdp)
    specs = input_specs(cfg, shape)

    with use_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = OptConfig()
            step = steps_mod.make_train_step(
                cfg, opt_cfg, unroll=unroll, remat=remat, q_chunk=q_chunk,
                chunk_unroll=chunk_unroll, n_micro=n_micro)
            o_sh = shd.opt_shardings(p_sh, mesh)
            b_sh = shd.data_shardings(mesh, specs["batch"])
            fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         donate_argnums=(0, 1))
            return fn.lower(abstract_params, _abstract_opt(abstract_params),
                            specs["batch"])
        if shape.kind == "prefill":
            step = steps_mod.make_prefill_step(
                cfg, unroll=unroll, q_chunk=q_chunk, chunk_unroll=chunk_unroll)
            b_sh = shd.data_shardings(mesh, specs["batch"])
            fn = jax.jit(step, in_shardings=(p_sh, b_sh))
            return fn.lower(abstract_params, specs["batch"])
        # decode
        step = steps_mod.make_serve_step(cfg, unroll=unroll)
        cache = specs["cache"]
        c_sh = shd.cache_shardings(cfg, cache, mesh, kv_variant=kv_variant)
        t_sh = NamedSharding(mesh, shd.batch_spec(mesh, shape.global_batch))
        fn = jax.jit(step, in_shardings=(p_sh, t_sh, c_sh), donate_argnums=(2,))
        return fn.lower(abstract_params, specs["token"], cache)


# ----------------------------------------------------------- secant cost fit
def _probe_layers(cfg: ModelConfig):
    if cfg.family == "hybrid":
        return (2, 3, 6)
    return (1, 2)


def _with_layers(cfg: ModelConfig, L: int) -> ModelConfig:
    return dataclasses.replace(cfg, name=f"{cfg.name}-probe{L}", n_layers=L)


def _reconstruct(cfg: ModelConfig, costs: Dict[int, float]) -> float:
    """Extrapolate a linear-in-depth cost to the full layer count."""
    if cfg.family == "hybrid":
        c2, c3, c6 = costs[2], costs[3], costs[6]
        sb = c6 - c3                      # per (rec,rec,attn) superblock
        base = c3 - sb
        rl = (c2 - base) / 2.0            # per remainder rec layer
        n_super, n_rem, _ = lm.hybrid_layout(cfg)
        return base + n_super * sb + n_rem * rl
    c1, c2 = costs[1], costs[2]
    pl = c2 - c1
    return c1 + (cfg.n_layers - 1) * pl


def cost_pass(cfg: ModelConfig, shape: ShapeSpec, mesh, *, fsdp: bool = True,
              remat: bool = True, q_chunk: Optional[int] = None,
              n_micro: int = 1, kv_variant: str = "auto") -> Dict[str, Any]:
    """Secant-extrapolated flops / bytes / collective bytes (per device)."""
    if q_chunk is None:
        q_chunk = default_q_chunk(cfg, shape)
    nc = (shape.seq_len // q_chunk) if (q_chunk and shape.kind != "decode") else 1
    metrics: Dict[int, Dict[str, float]] = {}
    for L in _probe_layers(cfg):
        pcfg = _with_layers(cfg, L)
        lowered = lower_cell(pcfg, shape, mesh, unroll=max(L, 1),
                             q_chunk=q_chunk, chunk_unroll=max(nc, 1),
                             fsdp=fsdp, remat=remat, n_micro=n_micro,
                             kv_variant=kv_variant)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
        metrics[L] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll_bytes": float(coll["total_bytes"]),
        }
    out = {}
    for key in ("flops", "bytes", "coll_bytes"):
        out[key] = max(_reconstruct(cfg, {L: m[key] for L, m in metrics.items()}),
                       0.0)
    out["probes"] = {str(L): m for L, m in metrics.items()}
    return out


# -------------------------------------------------------------------- driver
def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train (N = active params), 2·N·B decode."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # one token per sequence


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             out_dir: Optional[str] = None, fsdp: bool = True,
             remat: bool = True, q_chunk: Optional[int] = None,
             n_micro: int = 1, skip_cost: bool = False,
             tag: str = "", kv_variant: str = "auto",
             cfg_overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "multi_pod": multi_pod, "fsdp": fsdp, "n_micro": n_micro, "tag": tag,
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        _emit(rec, out_dir)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(mesh.size)
    t0 = time.time()
    lowered = lower_cell(cfg, shape, mesh, unroll=1, q_chunk=q_chunk,
                         chunk_unroll=1, fsdp=fsdp, remat=remat,
                         n_micro=n_micro, kv_variant=kv_variant)
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)
    ma = compiled.memory_analysis()
    print(ma)   # proves it fits (per-device bytes)
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "peak_bytes": int(ma.peak_memory_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    rec["memory"]["live_bytes"] = int(live)
    rec["fits_hbm_16g"] = bool(live < 16e9)
    ca = compiled.cost_analysis()
    print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
    coll_full = collective_bytes(compiled.as_text())
    rec["scan_hlo"] = {
        "flops_scanbody_once": float(ca.get("flops", 0.0)),
        "coll_bytes_scanbody_once": float(coll_full["total_bytes"]),
        "coll_counts": coll_full["count_by_kind"],
    }

    if not skip_cost:
        cost = cost_pass(cfg, shape, mesh, fsdp=fsdp, remat=remat,
                         q_chunk=q_chunk, n_micro=n_micro,
                         kv_variant=kv_variant)
        rec["cost"] = cost
        mf = model_flops(cfg, shape)
        fl_dev = cost["flops"]
        by_dev = cost["bytes"]
        cb_dev = cost["coll_bytes"]
        t_comp = fl_dev / PEAK_FLOPS
        t_mem = by_dev / HBM_BW
        t_coll = cb_dev / ICI_BW
        dom = max((t_comp, "compute"), (t_mem, "memory"), (t_coll, "collective"))
        rec["roofline"] = {
            "chips": n_chips,
            "flops_per_dev": fl_dev,
            "bytes_per_dev": by_dev,
            "coll_bytes_per_dev": cb_dev,
            "t_compute_s": t_comp,
            "t_memory_s": t_mem,
            "t_collective_s": t_coll,
            "dominant": dom[1],
            "bound_s": max(t_comp, t_mem, t_coll),
            "model_flops_total": mf,
            "model_flops_per_dev": mf / n_chips,
            "useful_flops_ratio": (mf / n_chips) / fl_dev if fl_dev else 0.0,
            "roofline_frac": (mf / n_chips / PEAK_FLOPS)
                             / max(t_comp, t_mem, t_coll)
                             if max(t_comp, t_mem, t_coll) > 0 else 0.0,
        }
    rec["status"] = "ok"
    _emit(rec, out_dir)
    return rec


def _emit(rec: Dict[str, Any], out_dir: Optional[str]):
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{rec['tag']}" if rec.get("tag") else ""
        path = os.path.join(
            out_dir, f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    status = rec.get("status")
    if status == "skipped":
        print(f"[dryrun] {rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:10s} "
              f"SKIP ({rec['reason'][:60]})")
    else:
        r = rec.get("roofline", {})
        print(f"[dryrun] {rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:10s} "
              f"OK compile={rec.get('compile_s')}s "
              f"peak={rec['memory']['peak_bytes']/1e9:.2f}GB "
              f"dom={r.get('dominant','-'):10s} "
              f"frac={r.get('roofline_frac', 0):.3f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--skip-cost", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--moe-ep", action="store_true")
    ap.add_argument("--attn-bf16", action="store_true")
    ap.add_argument("--moe-group", type=int, default=0)
    ap.add_argument("--rg-scan-bf16", action="store_true")
    ap.add_argument("--remat-policy", default="full", choices=("full", "dots"))
    ap.add_argument("--kv-variant", default="auto",
                    choices=("auto", "batch_model"))
    args = ap.parse_args()
    overrides = {}
    if args.moe_ep:
        overrides["moe_ep"] = True
    if args.attn_bf16:
        overrides["attn_av_bf16"] = True
    if args.moe_group:
        overrides["moe_group"] = args.moe_group
    if args.rg_scan_bf16:
        overrides["rg_scan_bf16"] = True
    if args.remat_policy != "full":
        overrides["remat_policy"] = args.remat_policy

    archs = sorted(all_configs()) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                             fsdp=not args.no_fsdp, remat=not args.no_remat,
                             q_chunk=args.q_chunk, n_micro=args.n_micro,
                             skip_cost=args.skip_cost, tag=args.tag,
                             kv_variant=args.kv_variant,
                             cfg_overrides=overrides or None)
                except Exception as e:  # noqa: BLE001 — report all cells
                    failures.append((arch, shape, mp, repr(e)[:200]))
                    print(f"[dryrun] {arch} {shape} mp={mp} FAIL: {e!r}"[:300])
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall cells OK")


if __name__ == "__main__":
    main()
