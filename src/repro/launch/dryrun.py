import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on the
production meshes and record memory/cost/collective analyses.

This file — and ONLY this file — fakes 512 host devices (the two lines above
run before any jax import, since jax locks the device count on first init).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
Artifacts land in artifacts/dryrun/<mesh>/<arch>__<shape>.json; the roofline
benchmark and EXPERIMENTS.md tables are generated from them.
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs.registry import ASSIGNED, get_config
from repro.distributed.sharding import use_mesh
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SHAPES, applicable, build_cell, correction_layer_counts


def _compile_cell(arch, shape, mesh, **kw):
    cell = build_cell(arch, shape, mesh, **kw)
    with use_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         donate_argnums=cell.donate_argnums)
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    return cell, compiled


def _costs_of(compiled):
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = rl.collective_bytes(hlo)
    coll_total = sum(v for k, v in coll.items() if not k.startswith("_"))
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll_total, "coll_kinds": coll}


def corrected_costs(arch, shape, mesh, remat, rules_overrides=(), softmax=None,
                    **cell_kw):
    """XLA's HLO cost analysis counts a scan body ONCE regardless of trip
    count (verified empirically), so scanned-layer cost is undercounted by
    ~n_layers. Fit cost(L) = intercept + slope*L from two small UNROLLED
    lowerings at full width, then extrapolate to the real layer count."""
    from repro.configs.registry import get_config as _gc
    cfg = _gc(arch)
    la, lb = correction_layer_counts(cfg)
    costs = []
    for l in (la, lb):
        _, comp = _compile_cell(arch, shape, mesh, remat=remat,
                                rules_overrides=rules_overrides,
                                softmax=softmax, n_layers_override=l,
                                scan_layers=False, **cell_kw)
        costs.append(_costs_of(comp))
    out = {}
    for key in ("flops", "bytes", "coll"):
        slope = (costs[1][key] - costs[0][key]) / (lb - la)
        out[key] = costs[0][key] + slope * (cfg.n_layers - la)
        out[key + "_per_layer"] = slope
    # kind-wise collective extrapolation
    kinds = {}
    for k in costs[0]["coll_kinds"]:
        if k.startswith("_"):
            continue
        slope = (costs[1]["coll_kinds"][k] - costs[0]["coll_kinds"][k]) / (lb - la)
        kinds[k] = costs[0]["coll_kinds"][k] + slope * (cfg.n_layers - la)
    out["coll_kinds"] = kinds
    return out


def run_cell(arch: str, shape: str, multi_pod: bool, remat: str = "full",
             rules_overrides: tuple = (), softmax=None,
             skip_correction: bool = False, **cell_kw) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    cell, compiled = _compile_cell(arch, shape, mesh, remat=remat,
                                   rules_overrides=rules_overrides,
                                   softmax=softmax, **cell_kw)
    t_compile = time.time() - t0
    t_lower = 0.0

    mem = compiled.memory_analysis()
    raw = _costs_of(compiled)
    if skip_correction:
        corr = {k: raw[k] for k in ("flops", "bytes", "coll")}
        corr["coll_kinds"] = raw["coll_kinds"]
    else:
        corr = corrected_costs(arch, shape, mesh, remat, rules_overrides,
                               softmax, **cell_kw)
    coll = corr["coll_kinds"]
    coll_total = corr["coll"]

    flops_pd = corr["flops"]
    bytes_pd = corr["bytes"]
    terms = rl.roofline_terms(flops_pd, bytes_pd, coll_total)

    meta = cell.meta
    tokens = meta["batch"] * meta["seq"]
    cfg = get_config(arch)
    attn_fl = 0.0
    if cfg.uses_attention and meta["kind"] == "train":
        attn_fl = 12.0 * cfg.n_layers * meta["seq"] * cfg.n_heads * cfg.d_head * tokens
    model_fl = (rl.model_flops_train(meta["active"], tokens, attn_fl)
                if meta["kind"] == "train" else float("nan"))

    result = {
        **meta,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "remat": remat,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": flops_pd,
        "bytes_per_device": bytes_pd,
        "collective_bytes_per_device": coll_total,
        "collectives": {k: v for k, v in coll.items()},
        "raw_uncorrected": {k: raw[k] for k in ("flops", "bytes", "coll")},
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "roofline": terms,
        "model_flops_global": model_fl,
        "useful_flops_ratio": rl.mfu_like(model_fl, flops_pd, n_chips)
        if meta["kind"] == "train" else None,
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    archs = ASSIGNED if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for multi in meshes:
        mesh_name = "multi" if multi else "single"
        outdir = os.path.join(args.out, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch in archs:
            cfg = get_config(arch)
            for shape in shapes:
                skip = applicable(cfg, SHAPES[shape])
                tag = f"{arch} x {shape} [{mesh_name}]"
                path = os.path.join(outdir, f"{arch}__{shape}.json")
                if skip:
                    print(f"SKIP  {tag}: {skip}")
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape,
                                   "skipped": skip}, f, indent=1)
                    continue
                try:
                    res = run_cell(arch, shape, multi, remat=args.remat)
                    r = res["roofline"]
                    print(f"OK    {tag}: compile={res['compile_s']}s "
                          f"flops/dev={res['flops_per_device']:.3e} "
                          f"peak_mem={res['memory']['peak_bytes']} "
                          f"dominant={r['dominant']} bound={r['bound_s']:.4f}s")
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                except Exception as e:
                    failures.append((tag, str(e)))
                    print(f"FAIL  {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" -", t, ":", e[:200])
        raise SystemExit(1)
    print("\nALL CELLS COMPILED.")


if __name__ == "__main__":
    main()
