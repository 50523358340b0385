"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory/cost/collective artifacts.

A CPU run over fake host devices, which the caller provides (XLA fixes
the device count when it starts, and this module never sets it):

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=512 \
  PYTHONPATH=src python -m repro.launch.dryrun --all

For every cell this emits a JSON record with:
  - compile status and wall time;
  - memory_analysis (XLA:CPU — NOTE: the CPU backend upcasts bf16 dot
    operands to f32, inflating bf16 temps ~2x vs a real TPU; we therefore
    also record a TPU-projected estimate computed from the HLO text's
    logical dtypes: argument bytes from the input specs + per-while-loop
    carry footprints);
  - trip-count-aware FLOPs / HBM bytes / collective bytes (hlo_analysis);
  - the three roofline terms vs the TPU v5e target (core/roofline).

Usage (with the environment above):
  python -m repro.launch.dryrun --arch qwen2_5_3b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod|--single-pod]
  python -m repro.launch.dryrun --all --both-meshes --out results/dryrun
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs.registry import ARCHS, SHAPES, get_cell
from repro.core.hlo_analysis import analyze_compiled_text, shape_bytes
from repro.core.napkin import analyze_cell as napkin_cell
from repro.core.roofline import build_report, model_flops
from repro.launch.cells import build_cell, lower_cell
from repro.launch.mesh import make_production_mesh

_WHILE_RE = re.compile(r"^\s*(?:ROOT\s+)?%[\w.\-]+ = (\(.*?\)) while\(",
                       re.M)


def _spec_bytes(tree) -> float:
    """Per-device argument bytes (uses each leaf's sharding)."""
    leaves = jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    total = 0.0
    for leaf in leaves:
        shape = leaf.shape
        sh = getattr(leaf, "sharding", None)
        if sh is not None:
            try:
                shape = sh.shard_shape(leaf.shape)
            except Exception:
                pass
        n = 1
        for d in shape:
            n *= d
        total += n * leaf.dtype.itemsize
    return total


def run_cell(arch: str, shape: str, *, multi_pod: bool,
             mesh=None) -> Dict[str, Any]:
    rec: Dict[str, Any] = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if multi_pod else "16x16"}
    cell = get_cell(arch, shape)
    if cell.skip_reason:
        rec["status"] = "skipped"
        rec["skip_reason"] = cell.skip_reason
        return rec
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    axis_names = tuple(mesh.axis_names)
    mesh_shape = tuple(mesh.devices.shape)
    t0 = time.time()
    try:
        built = build_cell(arch, shape, mesh)
        lowered = lower_cell(built)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    except Exception as exc:  # a failure here is a bug in the system
        rec["status"] = "FAILED"
        rec["error"] = f"{type(exc).__name__}: {exc}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        return rec

    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    # TPU-projected temp estimate: while-loop carries at logical dtype widths
    carries = sorted((shape_bytes(m) for m in _WHILE_RE.findall(txt)),
                     reverse=True)
    args_spec = _spec_bytes(built.args)
    cost = analyze_compiled_text(
        txt, mesh_shape, axis_names,
        peak_memory_bytes=(args_spec + sum(carries[:2])))

    cfg = built.cell.config
    if built.kind == "train":
        tokens = cell.shape.global_batch * cell.shape.seq_len
    elif built.kind == "prefill":
        tokens = cell.shape.global_batch * cell.shape.seq_len
    else:
        tokens = cell.shape.global_batch
    mf = model_flops(cfg.active_params(), tokens,
                     training=built.kind == "train")
    notes = []
    if built.dropped_rules:
        uniq = sorted({f"{l}={d}" for l, d in built.dropped_rules})
        notes.append("replicated(non-divisible): " + ",".join(uniq[:4]))
    report = build_report(
        arch=arch, shape=shape, mesh_shape=mesh_shape,
        axis_names=axis_names, cost=cost, model_flops_global=mf,
        notes="; ".join(notes))

    rec.update({
        "status": "ok",
        "kind": built.kind,
        "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1),
        "xla_mem": {
            "argument_gib": round(ma.argument_size_in_bytes / 2**30, 3),
            "output_gib": round(ma.output_size_in_bytes / 2**30, 3),
            "temp_gib": round(ma.temp_size_in_bytes / 2**30, 3),
            "note": "XLA:CPU inflates bf16 dot operands to f32",
        },
        "projected_mem": {
            "args_gib": round(args_spec / 2**30, 3),
            "top_carries_gib": [round(c / 2**30, 3) for c in carries[:4]],
            "peak_gib": round((args_spec + sum(carries[:2])) / 2**30, 3),
        },
        "hlo": {
            "flops_per_device": cost.flops,
            "hbm_bytes_per_device": cost.hbm_bytes,
            "collective_bytes_per_device": cost.collective_bytes(),
            "collectives_by_axes": {
                "/".join(k): v for k, v in
                cost.collective_bytes_by_axes().items()},
            "n_collectives": len(cost.collectives),
        },
        "roofline": report.row(),
    })
    nap = napkin_cell(cell, mesh_shape, axis_names)
    rec["napkin"] = {
        "t_compute_s": round(nap.t_compute, 6),
        "t_memory_s": round(nap.t_memory, 6),
        "t_collective_s": round(nap.t_collective, 6),
        "bound": nap.bound,
        "detail": {k: round(v, 4) if abs(v) < 1e6 else v
                   for k, v in nap.detail.items()},
    }
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="output dir for JSONL")
    args = ap.parse_args()

    meshes = [True, False] if args.both_meshes else [args.multi_pod]
    cells = []
    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                cells.append((arch, shape, mp))

    out_path = None
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        out_path = os.path.join(args.out, "dryrun.jsonl")

    need = 512 if any(meshes) else 256
    if jax.device_count() < need:
        raise SystemExit(
            f"dry-run needs {need} devices, have {jax.device_count()}: run "
            f"with JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count=512")
    mesh_cache = {}
    n_ok = n_skip = n_fail = 0
    for arch, shape, mp in cells:
        if mp not in mesh_cache:
            mesh_cache[mp] = make_production_mesh(multi_pod=mp)
        rec = run_cell(arch, shape, multi_pod=mp, mesh=mesh_cache[mp])
        status = rec["status"]
        n_ok += status == "ok"
        n_skip += status == "skipped"
        n_fail += status == "FAILED"
        if status == "ok":
            r = rec["roofline"]
            print(f"[{rec['mesh']}] {arch:18s} {shape:12s} ok "
                  f"compile={rec['t_compile_s']:6.1f}s "
                  f"bound={r['bound']:10s} t={r['t_bound_s']:.4f}s "
                  f"frac={r['roofline_frac']:.3f} "
                  f"mem≈{rec['projected_mem']['peak_gib']:.1f}GiB",
                  flush=True)
        elif status == "skipped":
            print(f"[{rec['mesh']}] {arch:18s} {shape:12s} SKIP "
                  f"({rec['skip_reason'][:60]}...)", flush=True)
        else:
            print(f"[{rec['mesh']}] {arch:18s} {shape:12s} FAILED: "
                  f"{rec['error'][:200]}", flush=True)
        if out_path:
            with open(out_path, "a") as fh:
                fh.write(json.dumps(rec) + "\n")
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, "
          f"{n_fail} failed", flush=True)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
