import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (same rule as dryrun.py).

"""§Perf hillclimbing: lower+compile optimized variants of the chosen
cells, record before/after against the baseline dry-run artifacts.

  PYTHONPATH=src python -m repro.launch.perf [--cell kimi_k2_1t:train_4k]
"""
import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_production_mesh
from repro.launch.dryrun import run_cell
from repro.launch.jax_cache import enable_compile_cache

# (cell, variant_tag, hypothesis, cfg_transform)
VARIANTS = []


def _v(cell, tag, hypothesis, transform):
    VARIANTS.append((cell, tag, hypothesis, transform))


# --- 1. kimi-k2-1t:train_4k — the scale cell (collective-bound baseline) ----

_v("kimi_k2_1t:train_4k", "local_dispatch",
   "MoE dispatch sorts all 1M global tokens -> GSPMD cross-shard sort + "
   "(E*C,d) dispatch buffers sized by GLOBAL capacity (~9.4GiB/dev). "
   "Shard-local dispatch (sort per data shard, experts combine via the "
   "existing TP reduce) should cut collective bytes severalfold and temp "
   "memory by ~dp x.",
   lambda cfg: dataclasses.replace(
       cfg, moe=dataclasses.replace(cfg.moe, dispatch="local")))

_v("kimi_k2_1t:train_4k", "local_bf16mom_remat",
   "On top of local dispatch: bf16 Adam moments halve optimizer HBM "
   "(state is the HBM floor for 1T params on 512 chips); remat of the "
   "flash scan + CE chunks trades ~5% recompute FLOPs for the transient "
   "backward buffers.",
   lambda cfg: dataclasses.replace(
       cfg, moe=dataclasses.replace(cfg.moe, dispatch="local"),
       opt_moment_dtype=jnp.bfloat16, remat_attn=True, remat_loss=True))

_v("kimi_k2_1t:train_4k", "shardmap_dispatch",
   "GSPMD cannot localise the batched dispatch (iter 1/2 refuted); a "
   "manually-partitioned shard_map interior — local sort, local gather, "
   "local expert FFN, ONE psum over 'model' — removes the dispatch "
   "all-to-all AND the replicated scatter buffers by construction.",
   lambda cfg: dataclasses.replace(
       cfg, moe=dataclasses.replace(cfg.moe, dispatch="shard_map")))

_v("kimi_k2_1t:train_4k", "shardmap_bf16mom_remat",
   "shard_map dispatch + bf16 moments + remat: the combined candidate.",
   lambda cfg: dataclasses.replace(
       cfg, moe=dataclasses.replace(cfg.moe, dispatch="shard_map"),
       opt_moment_dtype=jnp.bfloat16, remat_attn=True, remat_loss=True))

_v("kimi_k2_1t:train_4k", "global_bf16mom_remat",
   "Keep the (baseline) global dispatch — the local variant's scatter "
   "replication costs more than its all-to-all saves — and take the "
   "confirmed wins only: bf16 moments (optimizer HBM /2) + remat of "
   "flash/CE backward buffers.",
   lambda cfg: dataclasses.replace(
       cfg, opt_moment_dtype=jnp.bfloat16, remat_attn=True,
       remat_loss=True))

# --- 2. dimenet:ogb_products — most collective/memory-pathological ----------

_v("dimenet:ogb_products", "chunked_triplets",
   "The triplet gather materialises (T=247M, n_bilinear, d) in one shot "
   "(~422GiB/dev temp). Chunking the triplet list 64-way bounds the live "
   "set to 1/64 while keeping the same total gather traffic.",
   lambda cfg: dataclasses.replace(cfg, triplet_chunks=64))

_v("dimenet:ogb_products", "chunked_bf16_msgs",
   "Edge messages cross shards as f32; carrying the gather in bf16 halves "
   "the dominant all-gather bytes (collective term /2) at negligible "
   "accuracy cost for message passing.",
   lambda cfg: dataclasses.replace(cfg, triplet_chunks=64,
                                   msg_dtype=jnp.bfloat16))

# --- 3. stablelm-12b:train_4k — worst dense memory overshoot ----------------

_v("stablelm_12b:train_4k", "remat_attn_loss",
   "Baseline temp is 17.7GiB/dev (> 16GiB HBM): the backward keeps "
   "per-kv-block flash carries and per-chunk CE logits. Checkpointing "
   "both recomputes them in bwd: expect temp to drop below HBM with "
   "<=2 extra fwd passes of those subgraphs (compute term +~10%).",
   lambda cfg: dataclasses.replace(cfg, remat_attn=True, remat_loss=True))

_v("stablelm_12b:train_4k", "remat_bf16mom",
   "On top: bf16 moments halve optimizer state (24GiB global saved).",
   lambda cfg: dataclasses.replace(cfg, remat_attn=True, remat_loss=True,
                                   opt_moment_dtype=jnp.bfloat16))

_v("stablelm_12b:train_4k", "tp_only_params",
   "The dominant collective is the per-layer FSDP weight all-gather "
   "(2x per layer with remat). 12B params TP-16-sharded are only 1.5GiB "
   "bf16 per device, so FSDP buys nothing here: dropping it (fsdp=False) "
   "should remove those all-gathers (collective term down ~2x) at the "
   "cost of replicating params across the data axis.",
   lambda cfg: dataclasses.replace(cfg, remat_attn=True, remat_loss=True,
                                   opt_moment_dtype=jnp.bfloat16,
                                   fsdp=False))

# --- bonus: the paper's own workload -----------------------------------------

_v("dpc_grid:cc_1024", "no_mask_gather",
   "The CC exchange all-gathers labels AND masks, but masks == (labels>=0)"
   " — dropping the mask gather removes 20% of the ONE communication "
   "phase's bytes with bit-identical output (paper §6 'minimize the amount"
   " of ghost vertices which need to be sent').",
   lambda cfg: dataclasses.replace(cfg, gather_mask=False))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=None)
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()

    assert len(jax.devices()) == 512
    mesh = make_production_mesh(multi_pod=False)
    results = []
    for cell, tag, hypothesis, tr in VARIANTS:
        if args.cell and cell != args.cell:
            continue
        arch, shape = cell.split(":")
        print(f"[perf] {cell} :: {tag}\n  hypothesis: {hypothesis}",
              flush=True)
        try:
            rec = run_cell(arch, shape, mesh, "pod256", False, args.out,
                           cfg_transform=tr, tag=tag)
            rec["hypothesis"] = hypothesis
            rec["variant"] = tag
            with open(os.path.join(
                    args.out,
                    f"{arch}__{shape}__{tag}.json"), "w") as f:
                json.dump(rec, f, indent=1)
            results.append(rec)
        except Exception as e:  # noqa: BLE001
            print(f"[perf] FAIL {cell}:{tag}: {e}", flush=True)
    print(f"[perf] done: {len(results)} variants recorded")


if __name__ == "__main__":
    main()
