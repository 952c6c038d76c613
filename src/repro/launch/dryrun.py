import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) on the
production meshes and record memory/cost/roofline terms.

MUST set XLA_FLAGS before any jax import (above): jax locks the device
count on first init. Do not import this module from tests — run it as
``python -m repro.launch.dryrun``.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
"""

import argparse       # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402
from pathlib import Path  # noqa: E402

import jax            # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES, RunConfig, cell_supported,
                           get_config)                       # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402
from repro.launch.steps import build_step                    # noqa: E402
from repro.roofline.analysis import analyze, model_flops_for  # noqa: E402

DRYRUN_ARCHS = [a for a in ARCH_IDS if a != "rdmabox-paper-100m"]
# the production meshes are v5e pods; the host devices only stand in
TARGET_KIND = "TPU v5 lite"


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             run: RunConfig, hlo_dir=None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size
    t0 = time.perf_counter()
    try:
        with jax.set_mesh(mesh):
            jitted, args = build_step(cfg, shape, run, mesh)
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
        dt = time.perf_counter() - t0
        rep = analyze(compiled, arch=arch, shape_name=shape_name,
                      mesh_name=mesh_kind, chips=chips,
                      model_flops=model_flops_for(cfg, shape),
                      device_kind=TARGET_KIND, compile_seconds=dt)
        if hlo_dir is not None:
            path = Path(hlo_dir) / f"{arch}_{shape_name}_{mesh_kind}.hlo"
            path.write_text(compiled.as_text())
        out = rep.to_dict()
        out["status"] = "ok"
        return out
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--hlo-dir", default=None)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = DRYRUN_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    run = RunConfig(remat=args.remat)

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = {}
    if out_path.exists():
        results = {tuple(r["key"]): r for r in json.loads(out_path.read_text())}
    if args.hlo_dir:
        Path(args.hlo_dir).mkdir(parents=True, exist_ok=True)

    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                key = (arch, shape_name, mesh_kind)
                if args.skip_existing and key in results and \
                        results[key].get("status") in ("ok", "skipped"):
                    continue
                r = run_cell(arch, shape_name, mesh_kind, run, args.hlo_dir)
                r["key"] = list(key)
                r["remat"] = args.remat
                results[key] = r
                status = r["status"]
                extra = ""
                if status == "ok":
                    extra = (f"compute={r['compute_s']*1e3:.2f}ms "
                             f"memory={r['memory_s']*1e3:.2f}ms "
                             f"coll={r['collective_s']*1e3:.2f}ms "
                             f"dom={r['dominant']} "
                             f"frac={r['roofline_fraction']:.2f} "
                             f"[{r['compile_seconds']:.0f}s]")
                elif status == "error":
                    extra = r["error"][:160]
                print(f"[{mesh_kind}] {arch} × {shape_name}: {status} {extra}",
                      flush=True)
                out_path.write_text(json.dumps(list(results.values()), indent=1))

    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"\nDONE: {n_ok} ok, {n_skip} skipped (documented), {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
