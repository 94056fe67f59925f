"""Batched-serving launcher.

Two serving modes share this entry point:

  # LM prefill + decode loop with a KV cache (original mode)
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --batch 4 --prompt-len 32 --gen 16

  # Batched multi-tenant topology queries (DESIGN.md §Serve)
  PYTHONPATH=src python -m repro.launch.serve --topology --smoke \
      --requests 24 --repeat 2

  # Async deadline-aware plane, open-loop arrivals (DESIGN.md §Serve-v2)
  PYTHONPATH=src python -m repro.launch.serve --topology --async --smoke \
      --requests 24

  # Overload smoke: 4x-oversubscribed arrivals against tight admission
  # budgets; asserts typed rejections/sheds + parity (DESIGN.md §Serve-v3)
  PYTHONPATH=src python -m repro.launch.serve --topology --async --smoke \
      --requests 16 --overload
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.models import lm
from repro.launch.mesh import make_smoke_mesh
from repro.runtime.meshctx import use_mesh
from repro.launch.jax_cache import enable_compile_cache


def serve_lm(args):
    mod = configs.get(args.arch)
    cfg = mod.smoke_config() if args.smoke else mod.full_config()
    key = jax.random.PRNGKey(args.seed)
    params = lm.init_params(key, cfg)
    max_len = args.prompt_len + args.gen

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab)
    prefill = jax.jit(lambda p, t: lm.prefill(p, t, cfg, max_len=max_len))
    decode = jax.jit(lambda p, c, t: lm.decode_step(p, c, t, cfg),
                     donate_argnums=1)

    with use_mesh(make_smoke_mesh()):
        t0 = time.perf_counter()
        logits, cache = prefill(params, prompts)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        out = [jnp.argmax(logits, -1)[:, None]]
        t0 = time.perf_counter()
        for _ in range(args.gen - 1):
            logits, cache = decode(params, cache, out[-1])
            out.append(jnp.argmax(logits, -1)[:, None])
        jax.block_until_ready(logits)
        t_decode = time.perf_counter() - t0

    toks = np.asarray(jnp.concatenate(out, axis=1))
    tps = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"[serve] prefill {args.batch}x{args.prompt_len} in "
          f"{t_prefill * 1e3:.1f}ms; decode {args.gen - 1} steps at "
          f"{tps:.1f} tok/s (incl. compile)")
    print("[serve] sample continuation ids:", toks[0][:12])
    assert np.isfinite(np.asarray(logits)).all()
    return tps


def serve_topology(args):
    """Drive the batched topology engine over a synthetic mixed workload.

    `--repeat` replays the same request sequence (same layouts, so the same
    bucket occupancies), and the second pass is served entirely from the
    executable cache — the printed hit rate is the number to watch on
    repeated-layout traffic.
    """
    from repro.serve import TopologyEngine
    from repro.serve.workload import synthetic_requests

    mod = configs.get("serve_topology")
    cfg = mod.smoke_config() if args.smoke else mod.full_config()
    eng = TopologyEngine(min_extent=cfg.min_extent, max_batch=cfg.max_batch,
                         cache_capacity=cfg.cache_capacity,
                         slot_cost_cells=cfg.slot_cost_cells or None)

    t_total = 0.0
    n_total = 0
    for rep in range(args.repeat):
        reqs = synthetic_requests(
            args.requests, cfg.shapes, mix=cfg.mix,
            connectivity=cfg.connectivity, sweep_k=cfg.sweep_k,
            seed=args.seed)
        t0 = time.perf_counter()
        results = eng.submit_batch(reqs)
        dt = time.perf_counter() - t0
        t_total += dt
        n_total += len(results)
        info = eng.stats.as_dict()
        print(f"[serve-topology] pass {rep}: {len(results)} requests in "
              f"{dt * 1e3:.1f}ms ({len(results) / max(dt, 1e-9):.1f} req/s); "
              f"cumulative hit_rate={info['hit_rate']:.2f} "
              f"pad_fraction={info['pad_fraction']:.2f}")
    print("[serve-topology] engine stats:",
          json.dumps(eng.stats.as_dict(), sort_keys=True))
    return n_total / max(t_total, 1e-9)


def serve_topology_async(args):
    """Drive the async deadline-aware plane over a replayable open-loop
    trace (DESIGN.md §Serve-v2).

    Arrivals and deadlines come from a `WorkloadTrace` (printed at the end,
    so any run is replayable from its log alone).  Time runs on a
    `VirtualClock` with measured execution wall time charged into it, so
    deadline hits/misses reflect real execute cost while the arrival
    schedule stays deterministic.
    """
    from repro.serve import AsyncTopologyEngine, VirtualClock
    from repro.serve.workload import synthetic_trace

    mod = configs.get("serve_topology")
    cfg = mod.smoke_config() if args.smoke else mod.full_config()
    trace = synthetic_trace(
        args.requests, cfg.shapes, mix=cfg.mix,
        connectivity=cfg.connectivity, sweep_k=cfg.sweep_k, seed=args.seed,
        rate=args.rate if args.rate is not None else cfg.rate,
        deadline_slack=(args.deadline_slack if args.deadline_slack is not None
                        else cfg.deadline_slack))
    eng = AsyncTopologyEngine(
        min_extent=cfg.min_extent, max_batch=cfg.max_batch,
        cache_capacity=cfg.cache_capacity,
        slot_cost_cells=cfg.slot_cost_cells or None,
        clock=VirtualClock(), charge_execution_time=True,
        max_queue_depth=cfg.max_queue_depth,
        max_inflight_cells=cfg.max_inflight_cells,
        shed_policy=cfg.shed_policy)

    t0 = time.perf_counter()
    handles = []
    for req, (t, dl) in zip(trace.requests(), trace.arrivals):
        if t > eng.clock.now():
            eng.advance(t - eng.clock.now())
        handles.append(eng.submit(req, deadline=dl))
    # run time out to the deadline horizon first (so deadline flushes get
    # their chance), then drain whatever never came under pressure
    horizon = max((dl for _, dl in trace.arrivals if dl is not None),
                  default=eng.clock.now())
    if horizon > eng.clock.now():
        eng.advance(horizon - eng.clock.now())
    eng.drain()
    wall = time.perf_counter() - t0
    _check_handles(handles, allowed=())

    s = eng.stats
    assert (s.flush_capacity + s.flush_deadline + s.flush_drain
            + s.flush_retry == s.batches)
    lat = np.asarray(eng.latencies)
    p50, p99 = (float(np.percentile(lat, 50)), float(np.percentile(lat, 99))
                ) if lat.size else (0.0, 0.0)
    print(f"[serve-async] {len(handles)} requests in {wall * 1e3:.0f}ms wall "
          f"({len(handles) / max(wall, 1e-9):.1f} req/s incl. compile); "
          f"flushes capacity={s.flush_capacity} deadline={s.flush_deadline} "
          f"drain={s.flush_drain} retry={s.flush_retry}; "
          f"deadline_hit_rate={s.deadline_hit_rate:.2f}; "
          f"latency p50={p50 * 1e3:.1f}ms p99={p99 * 1e3:.1f}ms (virtual); "
          f"evictions={s.cache_evictions} queue_peak={s.queue_depth_peak}; "
          f"rejected={s.rejected} shed={s.shed}")
    print("[serve-async] engine stats:",
          json.dumps(eng.stats.as_dict(), sort_keys=True))
    print("[serve-async] replay trace:",
          json.dumps(trace.as_dict(), sort_keys=True))
    return len(handles) / max(wall, 1e-9)


def _check_handles(handles, allowed):
    """Fail the run unless every handle is done and every failed request
    failed with one of the `allowed` exception types (a compile or
    execution error on any request fails the smoke, not just a hang)."""
    pending = sum(not h.done() for h in handles)
    if pending:
        raise RuntimeError(f"{pending} of {len(handles)} requests never "
                           "completed")
    failed = [(i, h.exception()) for i, h in enumerate(handles)
              if h.exception() is not None
              and not isinstance(h.exception(), allowed)]
    if failed:
        i, exc = failed[0]
        raise RuntimeError(
            f"{len(failed)} of {len(handles)} requests failed; first "
            f"(request {i}): {exc!r}") from exc


def serve_topology_overload(args):
    """Overload smoke (DESIGN.md §Serve-v3): measure the sustainable
    closed-loop rate, then replay an open-loop trace at
    `cfg.overload_factor` times it against tight admission budgets with
    `shed_policy="hopeless"`, and assert the overload contract — the
    remainder is rejected/shed with TYPED errors only (nothing escapes the
    plane), and every request that did complete is bit-identical to the
    sequential `submit_many` facade.
    """
    from repro.serve import (AsyncTopologyEngine, TopologyEngine,
                             VirtualClock, SharedExecutableCache,
                             Overloaded, DeadlineShed)
    from repro.serve.workload import overload_trace
    from repro.topology import submit_many

    mod = configs.get("serve_topology")
    cfg = mod.smoke_config() if args.smoke else mod.full_config()

    # sustainable rate: warm closed-loop pass on a sync engine attached to
    # the SAME SharedExecutableCache the overload engine will use — the
    # measurement pass pays the compiles once and the overload run starts
    # warm, so its estimates reflect execute cost, not compile cost
    from repro.serve.workload import synthetic_requests
    cache = SharedExecutableCache(capacity=cfg.cache_capacity)
    reqs = synthetic_requests(
        args.requests, cfg.shapes, mix=cfg.mix,
        connectivity=cfg.connectivity, sweep_k=cfg.sweep_k, seed=args.seed)
    sync = TopologyEngine(min_extent=cfg.min_extent, max_batch=cfg.max_batch,
                          slot_cost_cells=cfg.slot_cost_cells or None,
                          compile_cache=cache, name="measure")
    sync.submit_batch(reqs)                       # cold (compiles)
    t0 = time.perf_counter()
    sync.submit_batch(reqs)                       # warm
    sustainable = len(reqs) / max(time.perf_counter() - t0, 1e-9)

    trace = overload_trace(
        args.requests, cfg.shapes, mix=cfg.mix,
        connectivity=cfg.connectivity, sweep_k=cfg.sweep_k, seed=args.seed,
        sustainable_rps=sustainable, factor=cfg.overload_factor)
    eng = AsyncTopologyEngine(
        min_extent=cfg.min_extent, max_batch=cfg.max_batch,
        cache_capacity=cfg.cache_capacity,
        slot_cost_cells=cfg.slot_cost_cells or None,
        clock=VirtualClock(), charge_execution_time=True,
        max_queue_depth=cfg.overload_queue_depth,
        max_inflight_cells=cfg.max_inflight_cells,
        shed_policy="hopeless", default_estimate=1.0 / sustainable,
        compile_cache=cache, name="overload")

    handles = []
    for req, (t, dl) in zip(trace.requests(), trace.arrivals):
        if t > eng.clock.now():
            eng.advance(t - eng.clock.now())
        handles.append(eng.submit(req, deadline=dl))
    eng.drain()

    s = eng.stats
    # the overload contract: only the typed admission/shed decisions may
    # fail a request
    _check_handles(handles, allowed=(Overloaded, DeadlineShed))
    assert s.rejected + s.shed > 0, \
        f"{cfg.overload_factor}x overload produced no rejections/sheds"
    assert s.completed + s.failures + s.shed == s.requests
    assert (s.flush_capacity + s.flush_deadline + s.flush_drain
            + s.flush_retry == s.batches)
    completed = [(i, h) for i, h in enumerate(handles)
                 if h.exception() is None]
    if completed:
        want = submit_many([h.request for _, h in completed])
        for (_, h), w in zip(completed, want):
            for f in ("labels", "ascending", "descending", "segmentation"):
                a, b = getattr(h.result(), f), getattr(w, f)
                assert (a is None) == (b is None)
                if a is not None:
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
    n = len(handles)
    print(f"[serve-overload] {n} requests at "
          f"{cfg.overload_factor:.0f}x sustainable "
          f"({sustainable:.1f} req/s): completed={s.completed} "
          f"rejected={s.rejected} (depth-limited={s.queue_depth_limit}) "
          f"shed={s.shed} failures={s.failures}; "
          f"parity held on all {len(completed)} completed; "
          f"shared cache compiles={cache.compiles} "
          f"(async engine reused {eng.stats.cache_hits})")
    print("[serve-overload] engine stats:",
          json.dumps(eng.stats.as_dict(), sort_keys=True))
    print("[serve-overload] replay trace:",
          json.dumps(trace.as_dict(), sort_keys=True))
    return s.rejected + s.shed


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--topology", action="store_true",
                    help="serve batched CC/MS topology queries instead of LM")
    ap.add_argument("--requests", type=int, default=24,
                    help="topology mode: requests per pass")
    ap.add_argument("--repeat", type=int, default=2,
                    help="topology mode: workload passes (2nd hits the "
                         "executable cache)")
    ap.add_argument("--async", dest="async_plane", action="store_true",
                    help="topology mode: async deadline-aware plane with "
                         "open-loop arrivals (DESIGN.md §Serve-v2)")
    ap.add_argument("--rate", type=float, default=None,
                    help="async mode: Poisson arrival rate (req/s); "
                         "defaults to the config's")
    ap.add_argument("--deadline-slack", type=float, default=None,
                    help="async mode: mean deadline slack (s); defaults "
                         "to the config's")
    ap.add_argument("--overload", action="store_true",
                    help="async mode: 4x-oversubscribed overload smoke "
                         "asserting typed rejections/sheds + parity "
                         "(DESIGN.md §Serve-v3)")
    args = ap.parse_args(argv)
    if args.topology and args.async_plane and args.overload:
        return serve_topology_overload(args)
    if args.topology and args.async_plane:
        return serve_topology_async(args)
    if args.topology:
        return serve_topology(args)
    return serve_lm(args)


if __name__ == "__main__":
    main()
