import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell this lowers + compiles the real
step program — train_step (train_4k), prefill_step (prefill_32k),
serve/decode_step (decode_32k, long_500k) — against the production meshes:

    single-pod  (16, 16)       ("data", "model")        256 chips
    multi-pod   (2, 16, 16)    ("pod", "data", "model") 512 chips

and records per cell: memory_analysis (fits?), cost_analysis
(per-device FLOPs/bytes), the collective schedule parsed from the
post-optimization HLO, and the probe-composed roofline inputs
(launch/probes.py).  Results go to results/dryrun/<arch>__<shape>__<mesh>.json
and EXPERIMENTS.md §Dry-run reads from them.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --mesh multi
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import base as cfg_base
from repro.configs.shapes import SHAPES, applicable, skip_reason
from repro.launch import hlo as hlo_lib
from repro.launch import probes as probes_lib
from repro.launch.mesh import chips, make_production_mesh
from repro.launch.steps import build_setup, rules_for

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results", "dryrun")


def out_dir():
    d = os.environ.get("DRYRUN_OUT", os.path.abspath(RESULTS))
    os.makedirs(d, exist_ok=True)
    return d


def run_probes(cfg, shape, rules, mesh):
    """Compile each per-unit probe; returns composed totals + breakdown."""
    if shape.kind == "train":
        probes = probes_lib.train_probes(cfg, shape, rules)
    elif shape.kind == "prefill":
        probes = probes_lib.prefill_probes(cfg, shape, rules)
    else:
        probes = probes_lib.decode_probes(cfg, shape, rules, mesh)
    total = {"flops": 0.0, "bytes_accessed": 0.0, "collective_bytes": 0.0}
    coll_by_kind = {}
    breakdown = []
    for p in probes:
        with jax.set_mesh(rules.mesh), probes_lib.probe_tracing():
            compiled = jax.jit(p.fn, in_shardings=p.in_shardings).lower(
                *p.arg_specs).compile()
        cs = hlo_lib.cost_summary(compiled)
        col = hlo_lib.collective_stats(compiled.as_text())
        item = {"name": p.name, "count": p.count, "flops": cs["flops"],
                "bytes_accessed": cs["bytes_accessed"],
                "collective": col.as_dict()}
        breakdown.append(item)
        total["flops"] += p.count * cs["flops"]
        total["bytes_accessed"] += p.count * cs["bytes_accessed"]
        total["collective_bytes"] += p.count * col.total_bytes
        for k, v in col.bytes_by_kind.items():
            coll_by_kind[k] = coll_by_kind.get(k, 0.0) + p.count * v
    if shape.kind == "train":
        opt = probes_lib.optimizer_analytic(
            cfg_count_params(cfg), chips(rules.mesh))
        total["flops"] += opt["flops"]
        total["bytes_accessed"] += opt["bytes_accessed"]
        breakdown.append({"name": "optimizer(analytic)", "count": 1,
                          **opt})
    total["collective_by_kind"] = coll_by_kind
    return total, breakdown


def cfg_count_params(cfg):
    return cfg.num_params()


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             *, skip_probes=False, force=False) -> dict:
    path = os.path.join(out_dir(),
                        f"{arch}__{shape_name}__{mesh_kind}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    cfg = cfg_base.get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "kind": shape.kind, "n_params": cfg.num_params(),
           "n_active_params": cfg.active_params()}
    if not applicable(cfg, shape_name):
        rec["status"] = "SKIP"
        rec["reason"] = skip_reason(cfg, shape_name)
        _write(path, rec)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = rules_for(cfg, mesh)
    rec["chips"] = chips(mesh)
    try:
        t0 = time.time()
        fn, arg_specs, in_sh, donate = build_setup(cfg, shape, mesh, rules)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh,
                              donate_argnums=donate).lower(*arg_specs)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0
        rec["lower_s"] = round(t_lower, 2)
        rec["compile_s"] = round(t_compile, 2)
        rec["cost"] = hlo_lib.cost_summary(compiled)
        rec["collective_schedule"] = hlo_lib.collective_stats(
            compiled.as_text()).as_dict()
        hbm = 16 * 2 ** 30   # v5e
        peak = rec["cost"]["peak_bytes_est"]
        rec["fits_hbm"] = bool(peak <= hbm)
        rec["peak_gb"] = round(peak / 2 ** 30, 2)
        if not skip_probes and mesh_kind == "single":
            # roofline terms are single-pod (contract); multi-pod proves
            # the pod axis shards.
            totals, breakdown = run_probes(cfg, shape, rules, mesh)
            rec["roofline_inputs"] = totals
            rec["probe_breakdown"] = breakdown
        rec["status"] = "OK" if rec["fits_hbm"] else "OK_OVER_HBM"
    except Exception as e:                      # noqa: BLE001
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    _write(path, rec)
    return rec


def _write(path, rec):
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--skip-probes", action="store_true")
    args = ap.parse_args()

    archs = cfg_base.list_configs() if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    rows = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                t0 = time.time()
                rec = run_cell(arch, shape, mk, force=args.force,
                               skip_probes=args.skip_probes)
                status = rec["status"]
                extra = ""
                if status.startswith("OK"):
                    extra = (f"peak {rec.get('peak_gb', '?'):>6} GB  "
                             f"compile {rec.get('compile_s', 0):6.1f}s")
                elif status == "SKIP":
                    extra = rec["reason"][:60]
                else:
                    extra = rec.get("error", "")[:90]
                print(f"{arch:25s} {shape:12s} {mk:6s} {status:12s} "
                      f"{extra}  [{time.time() - t0:5.1f}s]", flush=True)
                rows.append(rec)
    n_ok = sum(r["status"].startswith("OK") for r in rows)
    n_skip = sum(r["status"] == "SKIP" for r in rows)
    n_fail = sum(r["status"] == "FAIL" for r in rows)
    print(f"\n== dry-run: {n_ok} OK, {n_skip} SKIP, {n_fail} FAIL "
          f"of {len(rows)} cells ==")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
