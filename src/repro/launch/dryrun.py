import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and record memory/cost/collective analysis.

  PYTHONPATH=src python -m repro.launch.dryrun                  # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch kimi-k2-1t-a32b \
      --shape train_4k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --smoke          # tiny configs

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json.
"""
import argparse
import json
import re
import time
import traceback

import jax

from repro.launch.mesh import make_production_mesh
from repro.launch.cells import build_cell, all_cells
from repro.runtime.meshctx import use_mesh
from repro.launch.jax_cache import enable_compile_cache

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[0-9,]*\]\S*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the (partitioned,
    per-device) HLO.  'start' variants counted once; 'done' skipped."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    count = 0
    for m in _OP_RE.finditer(hlo_text):
        shape_txt, op = m.group(1), m.group(2)
        if f"{op}-done" in m.group(0):
            continue
        out[op] += _shape_bytes(shape_txt)
        count += 1
    out["n_collectives"] = count
    out["total"] = sum(v for k, v in out.items()
                       if k not in ("n_collectives", "total"))
    return out


def run_cell(arch, shape_name, mesh, mesh_label, smoke, out_dir,
             cfg_transform=None, tag=""):
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh, smoke=smoke,
                      cfg_transform=cfg_transform)
    with use_mesh(mesh):
        fn = jax.jit(cell.step_fn, in_shardings=cell.arg_shardings,
                     donate_argnums=cell.donate_argnums)
        lowered = fn.lower(*cell.arg_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # pre-0.4.31 jax: one dict per program
        cost = cost[0] if cost else {}
    coll = collective_bytes(compiled.as_text())
    rec = {
        "cell": cell.name, "mesh": mesh_label,
        "mesh_shape": dict(zip(mesh.axis_names,
                               [int(mesh.shape[a]) for a in mesh.axis_names])),
        "smoke": smoke,
        "memory": {
            k: int(getattr(mem, k, 0) or 0)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")
        },
        "cost": {k: float(v) for k, v in (cost or {}).items()
                 if isinstance(v, (int, float))},
        "collectives": coll,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "note": cell.note,
    }
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch.replace('-', '_')}__{shape_name}" + \
        (f"__{tag}" if tag else "") + ".json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1)
    per_dev = rec["memory"]["argument_size_in_bytes"] + \
        rec["memory"]["temp_size_in_bytes"]
    print(f"[dryrun] OK {cell.name} @ {mesh_label} "
          f"args+temp/dev={per_dev / 2**30:.2f}GiB "
          f"flops/dev={rec['cost'].get('flops', 0):.3e} "
          f"coll={coll['total'] / 2**20:.1f}MiB "
          f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)", flush=True)
    return rec


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    assert len(jax.devices()) == 512, "dry-run needs 512 host devices"
    meshes = []
    if args.single_pod or not args.multi_pod:
        meshes.append(("pod256", make_production_mesh(multi_pod=False)))
    if args.multi_pod or not args.single_pod:
        meshes.append(("pod2x256", make_production_mesh(multi_pod=True)))

    cells = all_cells()
    if args.arch:
        cells = [(a, s) for a, s in cells
                 if a == args.arch or a.replace("_", "-") == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]

    failures = []
    for label, mesh in meshes:
        out_dir = os.path.join(args.out, label)
        for arch, shape_name in cells:
            fname = os.path.join(
                out_dir, f"{arch.replace('-', '_')}__{shape_name}.json")
            if args.skip_existing and os.path.exists(fname):
                print(f"[dryrun] skip {arch}:{shape_name} @ {label}")
                continue
            try:
                run_cell(arch, shape_name, mesh, label, args.smoke, out_dir)
            except Exception as e:  # noqa: BLE001 — report, keep going
                failures.append((label, arch, shape_name, repr(e)))
                print(f"[dryrun] FAIL {arch}:{shape_name} @ {label}: {e}",
                      flush=True)
                traceback.print_exc()
    print(f"[dryrun] done; {len(failures)} failures")
    for f in failures:
        print("  FAIL:", *f)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
