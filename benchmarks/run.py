"""Benchmark harness — one function per paper table, plus framework
microbenches.  Prints ``name,us_per_call,derived`` CSV.

  tab1_strong_scaling — paper Tab. 1: MS segmentation + DPC-CC wall time vs
      shard count at fixed grid size (8 fake host devices, subprocess)
  tab2_weak_scaling   — paper Tab. 2: per-shard grid held constant
  tab3_threshold      — paper Tab. 3: implicit DPC-CC vs the VTK stand-in
      (label propagation + explicit extraction memory model) at top
      10% / 50% / 90% masks
  tab4_graph_cc_scaling — paper §5 unstructured path: distributed graph CC
      over vertex-partition counts {1,2,4,8} of a synthetic tet-mesh edge
      list vs the single-device oracle
  alg_doubling_vs_wave — the log(d) vs O(d) round-count gap that drives the
      paper's algorithm choice
  kernels             — Pallas hot-spot kernels vs their jnp oracles
  lm_train_microbench — framework-side: smoke-LM train-step latency
"""
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# JAX is imported inside the in-process benches only: a parent that has
# touched JAX holds the chip, and the scaling benches' worker processes
# need it (main() also runs those benches first).


def timeit(fn, *args, reps: int = 3):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6, out


_ROWS = []  # every _emit row; main() dumps the kernel/alg subset as JSON


def _emit(name, us, derived=""):
    _ROWS.append({"name": name, "us_per_call": round(us),
                  "derived": derived})
    print(f"{name},{us:.0f},{derived}", flush=True)


def _run_scaling_worker(worker_file, argv, *, multihost=False, name=""):
    """Spawn a scaling worker.  Default: 8 fake host devices (the CI
    single-host stand-in).  `multihost=True` instead hands the worker the
    REAL multi-process device set: the worker calls
    `jax.distributed.initialize()` (coordinator address / process ids come
    from the launcher env, e.g. srun or the JobSet controller) and layouts
    span the global device count — the path the paper's >= 64-rank tables
    need."""
    env = dict(os.environ)
    if not multihost:
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    worker = os.path.join(os.path.dirname(__file__), worker_file)
    cmd = [sys.executable, worker] + [str(a) for a in argv]
    if multihost:
        cmd.append("--multihost")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=3600)
    sys.stdout.write(proc.stdout)
    if proc.returncode:
        sys.stderr.write(proc.stderr)
        raise RuntimeError(f"{name or worker_file} worker failed")


def tab1_strong_scaling(base="96", multihost=False):
    """base: edge length or an exact "XxYxZ" size (e.g. 97x61x43) — passed
    through verbatim; non-divisible shapes run the pad-and-mask path and the
    report carries the per-block pad fraction."""
    _run_scaling_worker("_dpc_worker.py", ["strong", base],
                        multihost=multihost, name="strong-scaling")


def tab2_weak_scaling(base="48", multihost=False):
    _run_scaling_worker("_dpc_worker.py", ["weak", base],
                        multihost=multihost, name="weak-scaling")


def tab4_graph_cc_scaling(edge="24", multihost=False):
    """Unstructured CC strong scaling (paper §5, the graph path): vertex
    partitions {1, 2, 4, 8} of a synthetic tet-mesh edge list vs the
    single-device oracle; derived columns expose the one-phase cut-table
    exchange (ghost_bytes / comm_phases) and the owned-set pad fraction.
    edge: grid edge length or an exact "XxYxZ" size; counts that do not
    divide the partition count run the padded (imbalanced) path."""
    _run_scaling_worker("_graph_cc_worker.py", [edge],
                        multihost=multihost, name="graph-CC scaling")


def table_scaling(size="24", multihost=False):
    """Replicated vs sharded boundary table (DESIGN.md §Table-sharding):
    one grid across block lattices (2,) / (2, 2) / (2, 2, 2), manifold and
    CC, both table modes — the derived columns carry per-device table bytes
    and outer exchange rounds, and the worker writes BENCH_table.json (the
    artifact CI archives).  size: edge length or exact "XxYxZ", verbatim."""
    _run_scaling_worker("_table_worker.py", [size],
                        multihost=multihost, name="table-scaling")


def tab3_threshold(edge: int = 96):
    """Implicit DPC-CC vs label-propagation baseline across mask fractions;
    derived column carries the paper's memory argument: implicit needs ONE
    id array, explicit extraction materialises the masked edge list."""
    from repro.core.connected_components import connected_components_grid
    from repro.core.baseline_cc import label_propagation_grid
    from repro.data import perlin_noise
    import jax.numpy as jnp
    field = perlin_noise((edge, edge, edge), frequency=0.1, seed=3)
    n = field.size
    for frac, name in ((0.9, "top10"), (0.5, "top50"), (0.1, "top90")):
        mask = jnp.asarray(field > np.quantile(field, frac))
        us_dpc, res = timeit(
            lambda m: connected_components_grid(m, 6), mask, reps=2)
        us_lp, base = timeit(
            lambda m: label_propagation_grid(m, 6), mask, reps=2)
        assert (np.asarray(res.labels) == np.asarray(base.labels)).all()
        n_masked = int(mask.sum())
        implicit_mb = 4 * n / 2**20                   # one int32 label array
        explicit_mb = (2 * 4 * 6 * n_masked) / 2**20  # directed edge list
        _emit(f"tab3_dpc_implicit_{name}_{edge}", us_dpc,
              f"mem_mb={implicit_mb:.1f};rounds={int(res.n_rounds)}")
        _emit(f"tab3_baseline_wave_{name}_{edge}", us_lp,
              f"mem_mb={explicit_mb:.1f};rounds={int(base.n_rounds)}")


def alg_doubling_vs_wave(edge: int = 512):
    """2D snake: component diameter ~ n; pointer doubling needs O(log n)
    rounds, wave propagation O(n) — the core algorithmic claim."""
    from repro.core.connected_components import connected_components_grid
    from repro.core.baseline_cc import label_propagation_grid
    import jax.numpy as jnp
    mask = np.zeros((edge, 64), bool)
    mask[:, ::2] = True
    for i in range(0, 64 - 2, 4):                      # serpentine
        mask[-1, i:i + 2] = True
        mask[0, i + 2:i + 4] = True
    m = jnp.asarray(mask)
    us_dpc, res = timeit(lambda x: connected_components_grid(x, 4), m, reps=2)
    us_lp, base = timeit(lambda x: label_propagation_grid(x, 4), m, reps=2)
    assert (np.asarray(res.labels) == np.asarray(base.labels)).all()
    _emit(f"alg_pointer_doubling_snake_{edge}", us_dpc,
          f"compress_iters={int(res.n_compress_iter)}")
    _emit(f"alg_wave_propagation_snake_{edge}", us_lp,
          f"rounds={int(base.n_rounds)}")


def kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels.steepest_neighbor import steepest_neighbor
    from repro.kernels import ref
    from repro.core.steepest import neighbor_offsets
    rng = np.random.default_rng(0)
    order = jnp.asarray(rng.permutation(64 * 64 * 64)
                        .reshape(64, 64, 64).astype(np.int32))
    us_k, _ = timeit(lambda o: steepest_neighbor(o, 6, block_x=16,
                                                 interpret=True), order,
                     reps=1)
    us_r, _ = timeit(lambda o: ref.steepest_neighbor_ref(
        o, neighbor_offsets(3, 6)), order, reps=2)
    _emit("kernel_steepest_pallas_interp_64", us_k, "interpret=True")
    _emit("kernel_steepest_ref_64", us_r, "jnp oracle")

    # fused init vs the bit-exact host oracle (the parity assert keeps the
    # bench honest)
    from repro.kernels.fused_local_phase import fused_local_phase
    order32 = jnp.asarray(rng.permutation(32 * 32 * 32)
                          .reshape(32, 32, 32).astype(np.int32))
    us_fk, fp = timeit(
        lambda o: fused_local_phase(o, 6, mode="manifold", interpret=True),
        order32, reps=1)
    want = ref.fused_local_phase_ref(order32, 6, mode="manifold")
    assert (np.asarray(fp) == np.asarray(want)).all()
    us_fr, _ = timeit(lambda o: ref.fused_local_phase_ref(
        o, 6, mode="manifold"), order32, reps=1)
    _emit("kernel_fused_local_phase_pallas_interp_32", us_fk,
          "interpret=True")
    _emit("kernel_fused_local_phase_ref_32", us_fr, "host oracle")

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (1, 4, 256, 64))
    k = jax.random.normal(k2, (1, 4, 256, 64))
    v = jax.random.normal(k3, (1, 4, 256, 64))
    us_f, _ = timeit(lambda a, b, c: ref.flash_attention_ref(
        a, b, c, causal=True), q, k, v, reps=2)
    _emit("kernel_flash_ref_256", us_f, "chunked-softmax jnp")

    from repro.kernels.segment_bag import segment_bag
    from repro.models.bst import embedding_bag
    tab = jax.random.normal(jax.random.PRNGKey(4), (4096, 32))
    ids = jax.random.randint(jax.random.PRNGKey(5), (512, 16), -1, 4096)
    us_b, _ = timeit(lambda t_, i_: segment_bag(
        t_, i_, vocab_block=1024, batch_block=256, interpret=True), tab, ids,
        reps=1)
    us_r, _ = timeit(lambda t_, i_: embedding_bag(t_, i_), tab, ids, reps=2)
    _emit("kernel_segment_bag_pallas_interp", us_b, "interpret=True")
    _emit("kernel_segment_bag_ref", us_r, "take+segment_sum jnp")


def serve_throughput(n_requests: int = 24, repeat: int = 3,
                     arrival: str = "closed"):
    """Batched multi-tenant serving (DESIGN.md §Serve): replay one mixed
    CC / MS / manifold / threshold-sweep request sequence through the
    TopologyEngine.  Pass 0 compiles one executable per layout bucket; the
    remaining passes replay the same layouts and are served from the
    executable cache, so the warm row is the steady-state requests/sec.
    Derived columns carry the serving balance sheet: cache hit rate and the
    pad fraction of the bucketed layouts (the bounded-padding budget).
    Sizes come from configs/serve_topology.py smoke_config — the bench
    measures the serving layer (bucketing, batching, cache), not kernel
    FLOPs, so small prime extents are the interesting regime.

    `arrival="open"` additionally runs the async plane (DESIGN.md
    §Serve-v2): first the SAME closed burst through `AsyncTopologyEngine`
    (the apples-to-apples throughput comparison — the acceptance gate is
    that the async plane's bookkeeping does not cost warm req/s), then an
    open-loop pass with Poisson arrivals and per-request deadlines on a
    virtual clock with measured execution wall time charged in — the row
    that carries deadline-hit rate and latency percentiles.  The async rows
    land in BENCH_serve_async.json along with the replayable trace."""
    from repro import configs
    from repro.serve import TopologyEngine
    from repro.serve.workload import synthetic_requests

    cfg = configs.get("serve_topology").smoke_config()
    eng = TopologyEngine(min_extent=cfg.min_extent, max_batch=cfg.max_batch)
    reqs = synthetic_requests(n_requests, cfg.shapes, mix=cfg.mix,
                              connectivity=cfg.connectivity,
                              sweep_k=cfg.sweep_k, seed=0)
    t0 = time.perf_counter()
    eng.submit_batch(reqs)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(max(repeat - 1, 1)):
        eng.submit_batch(reqs)
    warm = (time.perf_counter() - t0) / max(repeat - 1, 1)
    s = eng.stats
    _emit(f"serve_throughput_cold_{n_requests}", cold / n_requests * 1e6,
          f"rps={n_requests / cold:.1f};hit_rate=0.00;"
          f"pad_fraction={s.pad_fraction:.2f}")
    _emit(f"serve_throughput_warm_{n_requests}", warm / n_requests * 1e6,
          f"rps={n_requests / warm:.1f};hit_rate={s.hit_rate:.2f};"
          f"pad_fraction={s.pad_fraction:.2f};executables={len(eng._exec)}")
    assert s.hit_rate >= 0.5, (
        f"repeated-layout hit rate {s.hit_rate:.2f} < 0.5")
    if arrival != "open":
        return

    from repro.serve import AsyncTopologyEngine, VirtualClock
    from repro.serve.workload import synthetic_trace
    sync_warm_rps = n_requests / warm

    # (1) closed burst through the async plane: identical executions once
    # warm, so any gap vs the sync engine is pure request-plane overhead
    aeng = AsyncTopologyEngine(min_extent=cfg.min_extent,
                               max_batch=cfg.max_batch,
                               clock=VirtualClock())

    def closed_pass():
        t0 = time.perf_counter()
        hs = [aeng.submit(r) for r in reqs]
        aeng.drain()
        assert all(h.done() for h in hs)
        return time.perf_counter() - t0

    closed_pass()                                     # compile
    warm_async = min(closed_pass() for _ in range(max(repeat - 1, 1)))
    async_rps = n_requests / warm_async
    _emit(f"serve_async_closed_warm_{n_requests}",
          warm_async / n_requests * 1e6,
          f"rps={async_rps:.1f};hit_rate={aeng.stats.hit_rate:.2f};"
          f"vs_sync={async_rps / sync_warm_rps:.2f}")

    # (2) open-loop: trace arrivals + deadlines, virtual time, execution
    # wall time charged into the clock so deadline hits reflect real cost
    trace = synthetic_trace(n_requests, cfg.shapes, mix=cfg.mix,
                            connectivity=cfg.connectivity,
                            sweep_k=cfg.sweep_k, seed=0, rate=cfg.rate,
                            deadline_slack=cfg.deadline_slack)
    oeng = AsyncTopologyEngine(min_extent=cfg.min_extent,
                               max_batch=cfg.max_batch,
                               cache_capacity=cfg.cache_capacity,
                               slot_cost_cells=cfg.slot_cost_cells or None,
                               clock=VirtualClock(),
                               charge_execution_time=True)

    def open_pass():
        base = oeng.clock.now()
        t0 = time.perf_counter()
        hs = []
        for req, (t, dl) in zip(trace.requests(), trace.arrivals):
            tt = base + t
            if tt > oeng.clock.now():
                oeng.advance(tt - oeng.clock.now())
            hs.append(oeng.submit(
                req, deadline=None if dl is None else base + dl))
        oeng.drain()
        assert all(h.done() for h in hs)
        return time.perf_counter() - t0

    open_pass()                                       # cold (compiles)
    n_cold = len(oeng.latencies)
    hits0, miss0 = oeng.stats.deadline_hits, oeng.stats.deadline_misses
    wall_open = open_pass()                           # warm, measured
    so = oeng.stats
    lat = np.asarray(oeng.latencies[n_cold:], dtype=float)
    p50 = float(np.percentile(lat, 50)) if lat.size else 0.0
    p99 = float(np.percentile(lat, 99)) if lat.size else 0.0
    warm_hits = so.deadline_hits - hits0
    warm_total = warm_hits + (so.deadline_misses - miss0)
    dhr = warm_hits / warm_total if warm_total else 1.0
    assert (so.flush_capacity + so.flush_deadline + so.flush_drain
            + so.flush_retry == so.batches)
    _emit(f"serve_async_open_warm_{n_requests}",
          wall_open / n_requests * 1e6,
          f"rps={n_requests / wall_open:.1f};deadline_hit_rate={dhr:.2f};"
          f"p50_ms={p50 * 1e3:.2f};p99_ms={p99 * 1e3:.2f};"
          f"evictions={so.cache_evictions};"
          f"queue_peak={so.queue_depth_peak}")

    # (3) overload: the SAME workload shape arriving at overload_factor x
    # the measured sustainable rate against tight admission budgets and
    # shed_policy="hopeless" (DESIGN.md §Serve-v3).  The engine attaches to
    # the open-loop engine's SharedExecutableCache, so it starts warm and
    # the row measures overload POLICY, not compile cost.  The reject/shed
    # rates are the bench's overload balance sheet.
    from repro.serve import PlaneError
    from repro.serve.workload import overload_trace
    otrace = overload_trace(n_requests, cfg.shapes, mix=cfg.mix,
                            connectivity=cfg.connectivity,
                            sweep_k=cfg.sweep_k, seed=1,
                            sustainable_rps=sync_warm_rps,
                            factor=cfg.overload_factor)
    xeng = AsyncTopologyEngine(min_extent=cfg.min_extent,
                               max_batch=cfg.max_batch,
                               slot_cost_cells=cfg.slot_cost_cells or None,
                               clock=VirtualClock(),
                               charge_execution_time=True,
                               max_queue_depth=cfg.overload_queue_depth,
                               max_inflight_cells=cfg.max_inflight_cells,
                               shed_policy="hopeless",
                               default_estimate=1.0 / sync_warm_rps,
                               compile_cache=oeng.cache, name="overload")
    t0 = time.perf_counter()
    ohs = []
    for req, (t, dl) in zip(otrace.requests(), otrace.arrivals):
        if t > xeng.clock.now():
            xeng.advance(t - xeng.clock.now())
        ohs.append(xeng.submit(req, deadline=dl))
    xeng.drain()
    wall_over = time.perf_counter() - t0
    sx = xeng.stats
    assert all(h.done() for h in ohs)
    for h in ohs:
        assert h.exception() is None or isinstance(h.exception(), PlaneError)
    assert sx.rejected + sx.shed > 0, (
        f"{cfg.overload_factor}x overload produced no rejections/sheds")
    assert sx.completed + sx.failures + sx.shed == sx.requests
    reject_rate = sx.rejected / n_requests
    shed_rate = sx.shed / n_requests
    _emit(f"serve_async_overload_{n_requests}",
          wall_over / n_requests * 1e6,
          f"factor={cfg.overload_factor:.0f};completed={sx.completed};"
          f"reject_rate={reject_rate:.2f};shed_rate={shed_rate:.2f};"
          f"depth_limited={sx.queue_depth_limit}")

    import json
    out = os.path.join(os.getcwd(), "BENCH_serve_async.json")
    with open(out, "w") as f:
        json.dump({
            "sync_warm_rps": sync_warm_rps,
            "async_closed_warm_rps": async_rps,
            "open_loop": {
                "warm_rps": n_requests / wall_open,
                "deadline_hit_rate": dhr,
                "latency_p50_ms": p50 * 1e3,
                "latency_p99_ms": p99 * 1e3,
                "flush_reasons": {
                    "capacity": so.flush_capacity,
                    "deadline": so.flush_deadline,
                    "drain": so.flush_drain,
                    "retry": so.flush_retry},
                "cache_evictions": so.cache_evictions,
                "queue_depth_peak": so.queue_depth_peak,
            },
            "overload": {
                "factor": cfg.overload_factor,
                "completed": sx.completed,
                "rejected": sx.rejected,
                "shed": sx.shed,
                "reject_rate": reject_rate,
                "shed_rate": shed_rate,
                "queue_depth_limit": sx.queue_depth_limit,
                "max_queue_depth": cfg.overload_queue_depth,
                "shared_cache": xeng.cache.info(),
                "trace": otrace.as_dict(),
            },
            "trace": trace.as_dict(),
        }, f, indent=2)
        f.write("\n")
    print(f"# wrote {out}", file=sys.stderr)
    # the acceptance gate, with head-room for CI timer noise: the async
    # plane must not cost warm throughput vs the synchronous engine
    assert async_rps >= 0.75 * sync_warm_rps, (
        f"async warm {async_rps:.1f} req/s < 0.75x sync warm "
        f"{sync_warm_rps:.1f} req/s")


def lm_train_microbench():
    import jax
    from repro import configs
    from repro.models import lm
    from repro.optim import adamw
    cfg = configs.get("llama3_2_1b").smoke_config()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw(1e-3)
    state = opt.init(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}

    @jax.jit
    def step(params, state, batch):
        (l, m), g = jax.value_and_grad(lm.loss_fn, has_aux=True)(
            params, batch, cfg)
        params, state, _ = opt.update(g, state, params)
        return params, state, l

    us, _ = timeit(lambda p, s, b: step(p, s, b), params, state, batch,
                   reps=3)
    _emit("lm_train_step_smoke_8x64", us, f"params={cfg.n_params()}")


# name -> (fn, default kwargs, --tiny kwargs for the CI smoke step).
# Tiny scaling sizes are PRIME on purpose: the nightly bench artifact then
# exercises the ragged pad-and-mask path on every layout.
_BENCHES = {
    "tab3_threshold": (tab3_threshold, {"edge": 64}, {"edge": 24}),
    "alg_doubling_vs_wave": (alg_doubling_vs_wave, {"edge": 256},
                             {"edge": 64}),
    "kernels": (kernels, {}, {}),
    "lm_train_microbench": (lm_train_microbench, {}, {}),
    "serve_throughput": (serve_throughput, {"n_requests": 24, "repeat": 3},
                         {"n_requests": 8, "repeat": 2}),
    "tab1_strong_scaling": (tab1_strong_scaling, {"base": 64},
                            {"base": 17}),
    "tab2_weak_scaling": (tab2_weak_scaling, {"base": 32}, {"base": 8}),
    "tab4_graph_cc_scaling": (tab4_graph_cc_scaling, {"edge": 24},
                              {"edge": 7}),
    "table_scaling": (table_scaling, {"size": 48}, {"size": 13}),
}

# benches that accept an exact user size via --size= (passed through
# verbatim — sizes are never rounded to divisible shapes)
_SIZED = {"tab1_strong_scaling": "base", "tab2_weak_scaling": "base",
          "tab4_graph_cc_scaling": "edge", "table_scaling": "size"}

# subprocess scaling benches that can run on a real multi-process mesh
_MULTIHOST = {"tab1_strong_scaling", "tab2_weak_scaling",
              "tab4_graph_cc_scaling", "table_scaling"}


def main(argv=None) -> None:
    """Usage: run.py [--tiny] [--size=XxYxZ] [--multihost] [bench ...] — no
    names runs everything.  --size passes the user's exact size through to
    the scaling benches (any extent: non-divisible shapes take the padded
    path and the report prints the pad fraction per block).  --multihost
    runs the subprocess scaling benches on the real multi-process device
    set via `jax.distributed.initialize()` (launcher env provides the
    coordinator) instead of 8 fake host devices.  Output is CSV on stdout
    (CI redirects it into an artifact)."""
    argv = sys.argv[1:] if argv is None else argv
    tiny = "--tiny" in argv
    multihost = "--multihost" in argv
    size = None
    arrival = "closed"
    for a in argv:
        if a.startswith("--size="):
            size = a.split("=", 1)[1]
        if a.startswith("--arrival="):
            arrival = a.split("=", 1)[1]
    if arrival not in ("closed", "open"):
        sys.exit(f"--arrival must be closed or open, got {arrival!r}")
    names = [a for a in argv if not a.startswith("-")]
    bad_flags = [a for a in argv
                 if a.startswith("-") and a not in ("--tiny", "--multihost")
                 and not a.startswith("--size=")
                 and not a.startswith("--arrival=")]
    if bad_flags:
        sys.exit(f"unknown flag(s) {bad_flags}; flags are --tiny, "
                 "--size=XxYxZ, --arrival=closed|open and --multihost")
    if multihost:
        non_mh = [n for n in (names or list(_BENCHES)) if n not in _MULTIHOST]
        if non_mh:
            sys.exit(f"--multihost only applies to {sorted(_MULTIHOST)}; "
                     f"drop {non_mh} or run them separately")
    unknown = [n for n in names if n not in _BENCHES]
    if unknown:
        sys.exit(f"unknown benchmark(s) {unknown}; "
                 f"available: {', '.join(_BENCHES)}")
    print("name,us_per_call,derived")
    # worker-spawning benches first, while this process is still off JAX
    for n in sorted(names or list(_BENCHES), key=lambda n: n not in _MULTIHOST):
        fn, full_kw, tiny_kw = _BENCHES[n]
        kw = dict(tiny_kw if tiny else full_kw)
        if size is not None and n in _SIZED:
            kw[_SIZED[n]] = size
        if n == "serve_throughput":
            kw["arrival"] = arrival
        if n in _MULTIHOST:
            kw["multihost"] = multihost
        fn(**kw)
    # kernel-facing rows also land in a JSON artifact (BENCH_kernels.json):
    # the fused-vs-unfused round counts are the acceptance numbers of the
    # fused-local-phase kernel, and JSON keeps them machine-comparable
    # across nightly runs without parsing the CSV
    kernel_rows = [r for r in _ROWS
                   if r["name"].startswith(("kernel_", "alg_"))]
    if kernel_rows:
        import json
        out = os.path.join(os.getcwd(), "BENCH_kernels.json")
        with open(out, "w") as f:
            json.dump({"rows": kernel_rows}, f, indent=2)
            f.write("\n")
        print(f"# wrote {out} ({len(kernel_rows)} kernel rows)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
