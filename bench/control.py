#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, for one cell.

    python3 bench/control.py --workload <cell> [--runs 3]

In one process on the cell's chips: the field is made as a run makes it,
the plain reference answers the query once, and then the program answers
it `--runs` times through the timed path (`Workload.query`).  Printed, as
one JSON line each:

* ``program``: one line per run, the mismatch counts of the program
  against the reference (the lower readings);
* ``control``: the same counts for the control, which is the reference put
  in the program's place and computed one precision below the float32
  that the configuration states: the field (and the cc threshold) rounded
  to bfloat16 before ordering or thresholding (the upper readings).

The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def to_bf16(x):
    """Round to bfloat16 (nearest even) and back to float32."""
    import ml_dtypes
    import numpy as np
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def counts(got: dict, want: dict) -> dict:
    import reference
    return {f"{k}_mismatch": reference.mismatches(got[k], v)
            for k, v in want.items()}


def readings(work, runs: int):
    """Yield the control's line, then one line per run of the program."""
    import jax
    work.setup(run.log)
    field = jax.device_get(work.field)
    want = work.reference(field)
    t = None if work.threshold is None else to_bf16(work.threshold)
    yield {"control": counts(work.reference(to_bf16(field), t), want)}
    for i in range(runs):
        out, _ = work.query()
        got = {k: jax.device_get(v) for k, v in out.items()}
        del out
        yield {"run": i, "program": counts(got, want)}


def main(argv=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=3)
    args = ap.parse_args(argv)

    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    cell, config_path, traffic_path = run.find_cell(spec, args.workload)
    run.enable_compile_cache()
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise run.NoChip(f"needs {cell['chips']} TPU chip(s)")
    sys.path.insert(0, str(run.ROOT / "src"))
    import workload as wl
    work = wl.Workload(run.load_json(config_path),
                       run.load_json(traffic_path),
                       devices[:cell["chips"]])
    rows = []
    for row in readings(work, args.runs):
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


if __name__ == "__main__":
    main()
