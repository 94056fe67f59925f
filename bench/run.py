#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in `BENCHMARK.json`; its configuration
and traffic mix are the files that entry names (`bench/configs/<name>.json`,
`bench/traffic/<name>.json`), and each per-layer metric is read by
`bench/metrics/<name>.py`.  Set-up makes the configuration's field on the
cell's chips and runs one untimed query on a field of the same shape that
takes almost no work, which loads every program the window runs; then
queries run back to back, one client in a closed loop, for `--seconds` (a
query that starts in the window runs to its end, and the window ends with
it).  After the window the last query's outputs are compared with the
plain reference (`bench/reference.py`) vertex by vertex, and every query's
fingerprint with the reference's.

The input is the configuration's field, a fixed window of the noise, for
every `--seed`: a run holds one query, and the field decides how many
rounds it takes, so a field drawn from the seed would change the work from
run to run.

The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the result's last key.  Without a TPU, or with fewer chips than
the cell needs, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_out" / "trace"
sys.path.insert(0, str(BENCH))

import peaks  # noqa: E402
import reference  # noqa: E402


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell needs: no result is printed."""

    def __init__(self, msg):
        super().__init__(3)
        self.msg = msg


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def find_cell(spec, name):
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return cell, ROOT / config["file"], \
        BENCH / "traffic" / f"{cell['traffic']}.json"


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_reader(name):
    """The reader of a per-layer metric: `bench/metrics/<name>.py`, where
    a name split by the end-to-end metric it moves (`local_iters.cc`) is
    read by the file of its first part."""
    path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache():
    """JAX's persistent cache at $JAX_COMPILATION_CACHE_DIR, else at the
    fixed `<checkout>/.jax_cache`; every program is cached, however short
    its compile, so a warm run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class Context:
    """What a per-layer metric reader sees: the cell, the window, the
    counters of every query, host spans and the reduced device trace."""

    def __init__(self, cell, config, traffic, n_queries, window_s, stats,
                 spans, trace, peak):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.n_queries, self.window_s = n_queries, window_s
        self.stats, self.spans = stats, spans
        self.trace, self.peak = trace, peak

    def counter(self, key):
        """A `DPCStats` counter of the last query, summed over its programs
        (an MS query runs one per manifold direction); None if absent."""
        st = self.stats[-1] if self.stats else None
        if not st:
            return None
        parts = st.values() if all(isinstance(v, dict) for v in st.values()) \
            else [st]
        vals = [p[key] for p in parts if key in p]
        return sum(vals) if vals else None


class LoadCounter:
    """Programs compiled, or loaded from the compile cache, while `on`."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kwargs):
        if self.on and event == self.EVENT:
            self.n += 1


def run(argv=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True,
                    help="the input is the configuration's field for "
                    "every seed (see above)")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_json(ROOT / "BENCHMARK.json")
    cell, config_path, traffic_path = find_cell(spec, args.workload)
    config, traffic = load_json(config_path), load_json(traffic_path)

    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise NoChip(f"needs {cell['chips']} TPU chip(s); JAX found "
                     f"{len(devices)} {dev.platform} device(s)")
    # an unknown TPU kind is an error before any work; a CPU run (tests
    # only) has no peaks, and its roofline shares read nothing
    peak = peaks.lookup(dev.device_kind) if dev.platform == "tpu" else None
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")

    sys.path.insert(0, str(ROOT / "src"))
    import workload as wl
    work = wl.Workload(config, traffic, devices[:cell["chips"]])
    used = list(work.mesh.devices.flat)
    work.setup(log)
    out, _ = work.query(work.warm_field())      # loads every program
    jax.block_until_ready(wl.device_fingerprint(out))
    del out
    loads = LoadCounter()
    setup_s = time.perf_counter() - T_START
    log(f"[setup] {setup_s:.3f}s")

    if args.trace:
        import devtrace
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        work.trace_spans = True
    fps, stats, failed_runs = [], [], 0
    out = None
    loads.on = True
    with jax.profiler.TraceAnnotation("window"):
        t0 = time.perf_counter()
        while True:
            out = None
            try:
                out, st = work.query()
            except Exception as e:  # a query that raises is a wrong answer
                log(f"[query] raised {type(e).__name__}: {e}")
                failed_runs += 1
                break
            fps.append(wl.device_fingerprint(out))
            stats.append(st)
            if time.perf_counter() - t0 >= args.seconds:
                break
        window_s = time.perf_counter() - t0
    loads.on = False
    n = len(fps)
    reduced = None
    if args.trace:
        jax.profiler.stop_trace()
        reduced = devtrace.reduce_dir(TRACE_DIR, used)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in used)
    log(f"[window] {n} queries in {window_s:.3f}s; peak "
        f"{peak_bytes} bytes; {loads.n} programs compiled or loaded")

    # --- correctness: after the window, with the program's state freed ---
    t_ref = time.perf_counter()
    fps = [{k: int(v) for k, v in fp.items()} for fp in fps]
    got = {k: jax.device_get(v) for k, v in out.items()} if out else {}
    field = jax.device_get(work.field)
    del out
    work.field = None
    want = work.reference(field)
    want_fp = wl.host_fingerprint(want)
    checks = {f"{k}_mismatch": {"value": reference.mismatches(got.get(k), v),
                                "limit": 0} for k, v in want.items()}
    wrong = sum(fp != want_fp for fp in fps) + failed_runs
    checks["answers_wrong"] = {"value": wrong, "limit": 0}
    correct = n > 0 and all(c["value"] <= c["limit"]
                            for c in checks.values())
    log(f"[reference] {time.perf_counter() - t_ref:.3f}s")

    ctx = Context(cell, config, traffic, n, window_s, stats,
                  work.spans, reduced, peak)
    values = {"setup_s": setup_s, "peak_hbm_gib": peak_bytes / 2**30}
    metrics = {}
    for m in spec["per_layer" if args.trace else "end_to_end"]:
        if not applies(m, cell["name"]):
            continue
        if args.trace:
            v = load_reader(m["name"])(ctx)
        elif m["name"].endswith("query_s"):     # per query kind
            v = window_s / n if n else None
        else:
            v = values[m["name"]]
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": n + failed_runs,
              "failed": wrong, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    for k, c in checks.items():
        log(f"[check] {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return result


def main():
    try:
        run()
    except NoChip as e:
        log(f"bench: {e.msg}; nothing was run")
        raise


if __name__ == "__main__":
    main()
