"""Multi-pod dry-run (deliverable (e)).

Lowers + compiles every (architecture × input shape) cell for the
production meshes — 16×16 (single pod) and 2×16×16 (two pods) — and
records memory_analysis / cost_analysis / collective schedule to JSON for
EXPERIMENTS.md §Dry-run and the §Roofline tables.

It runs on 512 forced host devices of the CPU backend and pins itself
there, so it never claims an accelerator. Roofline terms divide those
CPU-compiled counts by the peaks of ``TARGET``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch dlrm-mlperf --shape train_batch
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--jobs-filter lm]
"""
import argparse
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch.cells import build_cell
from repro.launch.common import CellOptions
from repro.launch.mesh import make_production_mesh
from repro.roofline import analysis as ra

REPORT_DIR = pathlib.Path(__file__).resolve().parents[3] / "reports" / "dryrun"
TARGET = "TPU v5 lite"  # the device_kind whose peaks bound the roofline terms


def _cost_of(compiled) -> dict:
    mem = compiled.memory_analysis()
    mem_d = {}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes", "peak_memory_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                mem_d[k] = int(v)
    cost = compiled.cost_analysis() or {}
    cost_d = {k: float(v) for k, v in cost.items()
              if isinstance(v, (int, float)) and k in
              ("flops", "bytes accessed", "transcendentals", "utilization")}
    hlo = compiled.as_text()
    coll = ra.collective_bytes(hlo)
    return {"mem": mem_d, "cost": cost_d, "coll": coll, "hlo_bytes": len(hlo)}


def _lm_layer_extrapolation(arch, shape_name: str, mesh, opts) -> dict | None:
    """XLA's cost_analysis counts a lax.scan body ONCE (verified), so scanned
    LM stacks under-report per-step flops/bytes/collectives. We compile
    UNROLLED 1- and 2-layer variants of the same arch: body = u2 - u1,
    total = u1 + (L-1)·body. memory_analysis still comes from the full
    scanned compile (true buffers)."""
    import dataclasses as _dc

    from repro.launch import lm_cell as _lm

    u = {}
    for nl in (1, 2):
        a2 = _dc.replace(arch, model=_dc.replace(arch.model, n_layers=nl, scan_layers=False))
        cell = _lm.build(a2, arch.shape(shape_name), mesh, opts)
        u[nl] = _cost_of(cell.lower().compile())
    L = arch.model.n_layers

    def extrap(f1: float, f2: float) -> float:
        body = max(f2 - f1, 0.0)
        return f1 + (L - 1) * body

    out = {
        "flops": extrap(u[1]["cost"].get("flops", 0.0), u[2]["cost"].get("flops", 0.0)),
        "bytes": extrap(u[1]["cost"].get("bytes accessed", 0.0),
                        u[2]["cost"].get("bytes accessed", 0.0)),
        "coll_bytes": extrap(float(u[1]["coll"]["total"]), float(u[2]["coll"]["total"])),
        "u1": {"flops": u[1]["cost"].get("flops", 0.0), "coll": u[1]["coll"]["total"]},
        "u2": {"flops": u[2]["cost"].get("flops", 0.0), "coll": u[2]["coll"]["total"]},
    }
    return out


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             opts: CellOptions = CellOptions(), tag: str = "",
             layer_extrapolate: bool = True, save_hlo: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    arch = get_config(arch_id)
    shape = arch.shape(shape_name)
    t0 = time.time()
    cell = build_cell(arch_id, shape_name, mesh, opts)
    t_build = time.time() - t0

    t0 = time.time()
    lowered = cell.lower()
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    full = _cost_of(compiled)
    mem_d, cost_d, coll, hlo_len = full["mem"], full["cost"], full["coll"], full["hlo_bytes"]

    flops = cost_d.get("flops", 0.0)
    hbm_bytes = cost_d.get("bytes accessed", 0.0)
    coll_bytes = float(coll["total"])
    extrap = None
    if arch.family == "lm" and layer_extrapolate:
        extrap = _lm_layer_extrapolation(arch, shape_name, mesh, opts)
        flops, hbm_bytes, coll_bytes = extrap["flops"], extrap["bytes"], extrap["coll_bytes"]

    chips = mesh.devices.size
    roof = ra.Roofline(
        flops=flops,
        hbm_bytes=hbm_bytes,
        coll_bytes=coll_bytes,
        chips=chips,
        target=TARGET,
        model_flops=ra.model_flops(arch, shape),
    )
    rec = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "tag": tag,
        "ok": True,
        "seconds": {"build": t_build, "lower": t_lower, "compile": t_compile},
        "memory_analysis_per_device": mem_d,
        "cost_analysis_per_device_raw": cost_d,
        "collectives_per_device_raw": coll,
        "scan_extrapolation": extrap,
        "roofline": roof.to_dict(),
        "hlo_bytes": hlo_len,
    }
    if save_hlo:
        import zstandard

        hdir = REPORT_DIR / "hlo"
        hdir.mkdir(parents=True, exist_ok=True)
        name = f"{arch_id}_{shape_name}_{rec['mesh']}{'_' + tag if tag else ''}.hlo.zst"
        (hdir / name.replace("/", "-")).write_bytes(
            zstandard.ZstdCompressor(level=3).compress(
                compiled.as_text().encode()))
    return rec


def save(rec: dict):
    REPORT_DIR.mkdir(parents=True, exist_ok=True)
    tag = f"_{rec['tag']}" if rec.get("tag") else ""
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{tag}.json".replace("/", "-")
    (REPORT_DIR / name).write_text(json.dumps(rec, indent=2, default=str))
    return REPORT_DIR / name


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true")
    p.add_argument("--filter", default="", help="substring filter on arch id")
    p.add_argument("--tag", default="", help="report filename tag (perf variants)")
    p.add_argument("--use-pallas", action="store_true")
    # §Perf hillclimb levers
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--remat-policy", default="full")
    p.add_argument("--no-zero1", action="store_true")
    p.add_argument("--sp-residual", action="store_true")
    p.add_argument("--fused-ce", action="store_true")
    p.add_argument("--compress-grads", action="store_true")
    p.add_argument("--attn-impl", default="chunked")
    p.add_argument("--capacity-slack", type=float, default=4.0)
    p.add_argument("--recv-slack", type=float, default=2.0)
    p.add_argument("--save-hlo", action="store_true",
                   help="save compiled HLO text (zstd) for offline re-accounting")
    args = p.parse_args(argv)
    # before any backend starts: the CPU backend only, with 512 devices
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 512)

    jobs = []
    if args.all:
        for aid in ARCH_IDS:
            if args.filter and args.filter not in aid:
                continue
            arch = get_config(aid)
            for s in arch.shapes:
                jobs.append((aid, s.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        jobs = [(args.arch, args.shape)]

    opts = CellOptions(
        use_pallas=args.use_pallas,
        remat=not args.no_remat,
        remat_policy=args.remat_policy,
        zero1=not args.no_zero1,
        sp_residual=args.sp_residual,
        fused_ce=args.fused_ce,
        compress_grads=args.compress_grads,
        attn_impl=args.attn_impl,
        capacity_slack=args.capacity_slack,
        recv_slack=args.recv_slack,
    )
    failures = 0
    for aid, sname in jobs:
        t0 = time.time()
        try:
            rec = run_cell(aid, sname, args.multi_pod, opts, tag=args.tag,
                           save_hlo=args.save_hlo)
            path = save(rec)
            r = rec["roofline"]
            print(f"OK   {aid:22s} {sname:14s} {rec['mesh']:8s} "
                  f"compile={rec['seconds']['compile']:6.1f}s "
                  f"bound={r['bound']:10s} step>={r['step_s_lower_bound']*1e3:9.3f}ms "
                  f"-> {path.name}", flush=True)
        except Exception as e:
            failures += 1
            rec = {"arch": aid, "shape": sname,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "tag": args.tag, "ok": False, "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
            save(rec)
            print(f"FAIL {aid:22s} {sname:14s} ({time.time()-t0:.0f}s): "
                  f"{type(e).__name__}: {str(e)[:200]}", flush=True)
    print(f"done: {len(jobs) - failures}/{len(jobs)} cells OK")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
