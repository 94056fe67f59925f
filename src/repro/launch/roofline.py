import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (same rule as dryrun.py).

"""Roofline analysis from the compiled dry-run artifacts (§Roofline).

Terms per (arch x shape) on the single-pod mesh, TPU v5e constants:
  compute    = HLO_FLOPs_per_device   / 197e12  FLOP/s
  memory     = HLO_bytes_per_device   / 819e9   B/s
  collective = collective_bytes/device / 50e9   B/s (result-shape sum over
               all-gather/all-reduce/reduce-scatter/all-to-all/permute)

`lax.scan` bodies are cost-analyzed ONCE by XLA, so layer-scanned models
(LM archs, MeshGraphNet) are corrected by lowering L=1 and L=2 variants:
  metric(L) = m1 + (L-1) * (m2 - m1).
DPC cells iterate data-dependent `while` loops; their terms are PER
DOUBLING ROUND (noted in the table).

  PYTHONPATH=src python -m repro.launch.roofline            # full table
  PYTHONPATH=src python -m repro.launch.roofline --arch kimi-k2-1t-a32b
"""
import argparse
import dataclasses
import json

import jax
from repro.launch.jax_cache import enable_compile_cache

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_SCANNED = {"lm": "n_layers", "gnn-mgn": "n_layers"}


def _load(out_dir, arch, shape):
    p = os.path.join(out_dir, f"{arch.replace('-', '_')}__{shape}.json")
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return json.load(f)


def _metrics(rec):
    return {
        "flops": rec["cost"].get("flops", 0.0),
        "bytes": rec["cost"].get("bytes accessed", 0.0),
        "coll": float(rec["collectives"]["total"]),
        "transc": rec["cost"].get("transcendentals", 0.0),
    }


def _lower_variant(arch, shape, mesh, n_layers):
    from repro.launch.dryrun import collective_bytes
    from repro.launch.cells import build_cell
    from repro.runtime.meshctx import use_mesh

    def tr(cfg):
        # unroll == n_layers inlines the scan body n_layers times, so the
        # cost analysis really scales with the layer count
        return dataclasses.replace(cfg, n_layers=n_layers,
                                   scan_unroll=n_layers)

    cell = build_cell(arch, shape, mesh, cfg_transform=tr)
    with use_mesh(mesh):
        fn = jax.jit(cell.step_fn, in_shardings=cell.arg_shardings,
                     donate_argnums=cell.donate_argnums)
        compiled = fn.lower(*cell.arg_shapes).compile()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll": float(coll["total"]),
            "transc": cost.get("transcendentals", 0.0)}


def scan_corrected_metrics(arch, shape, mesh, rec, cache_dir):
    """metric(L) = m1 + (L-1)(m2 - m1) via L=1/L=2 lowers (cached)."""
    from repro import configs
    cfg = configs.get(arch).full_config()
    L = cfg.n_layers
    cpath = os.path.join(cache_dir,
                         f"{arch.replace('-', '_')}__{shape}__scancorr.json")
    if os.path.exists(cpath):
        with open(cpath) as f:
            c = json.load(f)
    else:
        m1 = _lower_variant(arch, shape, mesh, 1)
        m2 = _lower_variant(arch, shape, mesh, 2)
        c = {"m1": m1, "m2": m2}
        os.makedirs(cache_dir, exist_ok=True)
        with open(cpath, "w") as f:
            json.dump(c, f)
    out = {}
    for k in ("flops", "bytes", "coll", "transc"):
        body = c["m2"][k] - c["m1"][k]
        out[k] = c["m1"][k] + max(body, 0.0) * (L - 1)
    return out


def model_flops(arch, shape_name, shape, n_devices):
    """6*N*D train / 2*N*D serving (per the assignment's definition),
    N = active params; LM-family only (— for others)."""
    from repro import configs
    mod = configs.get(arch)
    if mod.FAMILY != "lm":
        return None
    cfg = mod.full_config()
    n_act = cfg.n_active_params()
    if shape["kind"] == "train":
        d = shape["batch"] * shape["seq"]
        total = 6 * n_act * d
    elif shape["kind"] == "prefill":
        total = 2 * n_act * shape["batch"] * shape["seq"]
    else:  # decode: one token per sequence
        total = 2 * n_act * shape["batch"]
    return total / n_devices


def analyze_cell(arch, shape_name, rec, mesh, cache_dir):
    from repro import configs
    mod = configs.get(arch)
    shape = mod.SHAPES[shape_name]
    n_dev = 1
    for v in rec["mesh_shape"].values():
        n_dev *= v
    m = _metrics(rec)
    corrected = False
    if mod.FAMILY == "lm" or (mod.FAMILY == "gnn"
                              and getattr(mod.full_config(), "arch", "")
                              == "meshgraphnet"):
        try:
            m = scan_corrected_metrics(arch, shape_name, mesh, rec, cache_dir)
            corrected = True
        except Exception as e:  # noqa: BLE001
            print(f"[roofline] scan-correction failed for "
                  f"{arch}:{shape_name}: {e}; using raw HLO metrics")
    t_comp = m["flops"] / PEAK_FLOPS
    t_mem = m["bytes"] / HBM_BW
    t_coll = m["coll"] / ICI_BW
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(arch, shape_name, shape, n_dev)
    ratio = (mf / m["flops"]) if (mf and m["flops"]) else None
    bound = max(terms.values())
    roofline_frac = (mf / PEAK_FLOPS) / bound if (mf and bound) else None
    return {
        "cell": f"{arch}:{shape_name}", "family": mod.FAMILY,
        "hlo_flops_dev": m["flops"], "hlo_bytes_dev": m["bytes"],
        "coll_bytes_dev": m["coll"], **terms,
        "dominant": dominant.replace("_s", ""),
        "model_flops_dev": mf, "useful_flops_ratio": ratio,
        "roofline_fraction": roofline_frac,
        "scan_corrected": corrected,
        "note": rec.get("note", ""),
    }


def fmt_table(rows):
    hdr = ("| cell | compute s | memory s | collective s | dominant | "
           "MODEL/HLO | roofline frac |\n|---|---|---|---|---|---|---|")
    out = [hdr]
    for r in rows:
        ratio = f"{r['useful_flops_ratio']:.2f}" \
            if r["useful_flops_ratio"] else "—"
        frac = f"{r['roofline_fraction']:.3f}" \
            if r["roofline_fraction"] else "—"
        out.append(
            f"| {r['cell']} | {r['compute_s']:.4f} | {r['memory_s']:.4f} | "
            f"{r['collective_s']:.4f} | **{r['dominant']}** | {ratio} | "
            f"{frac} |")
    return "\n".join(out)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--dryrun-dir", default="experiments/dryrun/pod256")
    ap.add_argument("--cache-dir", default="experiments/roofline/scancorr")
    ap.add_argument("--out", default="experiments/roofline/roofline.json")
    args = ap.parse_args()

    from repro.launch.mesh import make_production_mesh
    from repro.launch.cells import all_cells
    mesh = make_production_mesh(multi_pod=False)

    rows = []
    for arch, shape_name in all_cells():
        if args.arch and arch not in (args.arch,
                                      args.arch.replace("-", "_")):
            continue
        if args.shape and shape_name != args.shape:
            continue
        rec = _load(args.dryrun_dir, arch, shape_name)
        if rec is None:
            print(f"[roofline] missing dry-run for {arch}:{shape_name}")
            continue
        row = analyze_cell(arch, shape_name, rec, mesh, args.cache_dir)
        rows.append(row)
        print(f"[roofline] {row['cell']}: comp={row['compute_s']:.4f}s "
              f"mem={row['memory_s']:.4f}s coll={row['collective_s']:.4f}s "
              f"-> {row['dominant']}"
              + (f" frac={row['roofline_fraction']:.3f}"
                 if row['roofline_fraction'] else ""), flush=True)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    with open(args.out.replace(".json", ".md"), "w") as f:
        f.write(fmt_table(rows) + "\n")
    print(f"[roofline] wrote {len(rows)} rows -> {args.out}")


if __name__ == "__main__":
    main()
