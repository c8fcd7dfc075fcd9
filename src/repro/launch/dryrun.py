"""Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/collective analyses.

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out artifacts/dryrun

Each successful cell writes ``<out>/<arch>__<shape>__<mesh>.json`` with
memory_analysis, cost_analysis, collective bytes, and roofline terms.
No arrays are ever allocated (ShapeDtypeStruct end to end).
"""
import argparse
import os
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax

from ..hostdevices import force_host_device_count
from ..configs import SHAPES, get_arch, list_archs
from . import hlo_analysis
from .mesh import make_production_mesh
from .roofline import probe_roofline
from .specs import build_cell, optimized_cell_config


def run_cell(
    arch_name: str,
    shape_name: str,
    multi_pod: bool,
    out_dir: str,
    overrides: Optional[Dict[str, Any]] = None,
    tag: str = "",
    probe: bool = True,
    rules=None,
    opt: bool = False,
) -> Dict[str, Any]:
    arch = get_arch(arch_name)
    ok, reason = arch.applicable(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: Dict[str, Any] = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_name,
        "status": "skip", "reason": reason,
    }
    name = f"{arch_name}__{shape_name}__{mesh_name}{tag}"
    if not ok:
        _write(out_dir, name, rec)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    if opt:
        opt_rules, opt_ov = optimized_cell_config(arch, shape_name, mesh)
        rules = rules or opt_rules
        overrides = {**opt_ov, **(overrides or {})}
        rec["optimized"] = True
    t0 = time.perf_counter()
    try:
        # 1) production (scanned) build: THE compile-success proof + memory
        cell = build_cell(arch, shape_name, mesh, overrides=overrides,
                          analysis_mode=False, rules=rules)
        with mesh, jax.set_mesh(mesh):
            jitted = jax.jit(
                cell.fn,
                in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
            )
            lowered = jitted.lower(*cell.args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        coll_scan = hlo_analysis.collective_bytes(compiled.as_text())

        rec.update({
            "status": "ok",
            "meta": cell.meta,
            "t_lower_s": round(t_lower, 2),
            "t_compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "total_per_device_gb": round(
                    (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
                    / 2**30, 3),
                "fits_16gb_hbm": bool(
                    mem.argument_size_in_bytes + mem.temp_size_in_bytes
                    < 16 * 2**30),
            },
            "collective_schedule_scanned_hlo": coll_scan,
        })

        # 2) probe-extrapolated cost metrics (single-pod roofline table only)
        if probe:
            pr = probe_roofline(
                arch, shape_name, mesh, overrides=overrides or None,
                rules=rules,
            )
            n_chips = mesh.devices.size
            # MODEL_FLOPS: 6·N·D for training (fwd+bwd), 2·N·D for inference
            flops_per_param_token = 6.0 if cell.meta["kind"] == "train" else 2.0
            model_flops = (flops_per_param_token
                           * cell.meta["active_params"] * _tokens(cell.meta))
            hlo_total = pr["est"]["flops"] * n_chips
            rec.update({
                "cost": {
                    "flops_per_device": pr["est"]["flops"],
                    "bytes_per_device": pr["est"]["bytes"],
                },
                "collectives": {
                    k.replace("coll_", ""): v
                    for k, v in pr["est"].items() if k.startswith("coll_")
                },
                "roofline": pr["roofline"],
                "probes": pr["probes"],
                "model_flops_total": model_flops,
                "hlo_flops_total": hlo_total,
                "useful_flops_ratio": (
                    model_flops / hlo_total if hlo_total else 0.0
                ),
            })
    except Exception as e:  # record failures — they are bugs to fix
        rec.update({
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        })
    _write(out_dir, name, rec)
    return rec


def _tokens(meta: Dict[str, Any]) -> float:
    if meta["kind"] == "train":
        return meta["seq_len"] * meta["global_batch"]
    if meta["kind"] == "prefill":
        return meta["seq_len"] * meta["global_batch"]
    return meta["global_batch"]  # decode: one token per sequence


def _write(out_dir: str, name: str, rec: Dict[str, Any]) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    # 512 forced CPU devices for the production meshes; set before jax
    # creates its backend (importing jax does not)
    force_host_device_count(os.environ, 512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip probe-based cost extrapolation")
    ap.add_argument("--opt", action="store_true",
                    help="use the winning §Perf configuration per cell")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_err = 0
    for a in archs:
        for s in shapes:
            for mp in meshes:
                # probes feed the single-pod roofline table only
                rec = run_cell(a, s, mp, args.out,
                               probe=(not args.no_probe) and not mp,
                               opt=args.opt)
                tag = rec["status"]
                n_ok += tag == "ok"
                n_skip += tag == "skip"
                n_err += tag == "error"
                extra = ""
                if tag == "ok":
                    dom = rec.get("roofline", {}).get("dominant", "-")
                    extra = (f" dom={dom}"
                             f" mem={rec['memory']['total_per_device_gb']}GB"
                             f" compile={rec['t_compile_s']}s")
                elif tag == "error":
                    extra = " " + rec["error"][:120]
                elif tag == "skip":
                    extra = " " + rec["reason"]
                print(f"[{tag:5s}] {a} {s} "
                      f"{'multi' if mp else 'single'}{extra}", flush=True)
    print(f"done: {n_ok} ok, {n_skip} skip, {n_err} error", flush=True)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
