#!/usr/bin/env python3
"""Chip smoke: the paper's §5 workload on one TPU chip, through the entry
points a user calls.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the distributed path on four chips

One chip: a 512^3 Perlin field (frequency 0.1, made from --seed) is ranked
into an order field on the device; then Morse-Smale segmentation and the
top-10% connected components run through `repro.topology.submit` with
``backend="distributed"`` on a one-device mesh, and through
`TopologyEngine.submit_batch`.  Every result is compared bit for bit with
``backend="pure"`` on the same chip and checked on the device: every label
is a root (``label[label[v]] == label[v]``), every manifold label is a
critical vertex, every cc label is a masked vertex no smaller than its
members.  A 64^3 run is also compared with the numpy oracles of
`tests/oracles.py`.

``--chips 4``: only the 512^3 MS and CC through ``backend="distributed"``
on layouts (4,) and (2, 2), in both boundary-table modes, each compared bit
for bit with the one-device pure result computed in this process on
``jax.devices()[0]``.

Everything runs in this one process.  Without a TPU the script exits
non-zero before any work; any failed phase or check raises, so the exit
code is non-zero and the last line is not printed.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, "src"))
sys.path.insert(0, os.path.join(_HERE, "tests"))

SIZE = 512               # configs/dpc_grid.py SHAPES["grid_512"/"cc_512"]
ORACLE_SIZE = 64
FREQUENCY = 0.1          # paper §5
LAYOUTS_4 = ((4,), (2, 2))
TABLE_MODES = ("replicated", "sharded")


def log(msg):
    print(msg, flush=True)


class CompileClock:
    """Seconds of XLA compilation (JAX's backend-compile monitoring event);
    a phase's "run" time is its wall time minus this, so it still holds
    tracing and host work."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration


class Phase:
    """Times one phase: wall, compile and run seconds on their own line."""

    def __init__(self, clock, name):
        self.clock, self.name = clock, name

    def __enter__(self):
        self.c0, self.t0 = self.clock.total, time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            wall = time.perf_counter() - self.t0
            comp = self.clock.total - self.c0
            log(f"[time] {self.name}: wall {wall:.3f}s xla_compile "
                f"{comp:.3f}s (summed over threads) run "
                f"{max(wall - comp, 0.0):.3f}s")
        return False


def warm(clock, name, jobs):
    """Compile `jobs` ((jitted function, args) pairs) concurrently, ahead of
    time: each executable lands in the persistent compile cache, where the
    entry-point call that follows finds it.  XLA compiles one program on a
    few threads; several at once use the host's other cores."""
    from concurrent.futures import ThreadPoolExecutor
    with Phase(clock, name), ThreadPoolExecutor(len(jobs)) as ex:
        list(ex.map(lambda job: job[0].lower(*job[1]).compile(), jobs))


def grid_jobs(mesh, order, mask, table_modes=TABLE_MODES[:1]):
    """The distributed programs `submit` will run for this mesh."""
    from repro.core.distributed import _decomp_for, _grid_program
    coords = _decomp_for(mesh, order.shape).boundary_coords_dev
    jobs = []
    for tm in table_modes:
        jobs.append((_grid_program("manifold", mesh, order.shape, False, 6,
                                   True, "auto", tm, 64), (order,)))
        jobs.append((_grid_program("cc", mesh, mask.shape, False, 6, True,
                                   "auto", tm, 64), (mask, coords)))
    return jobs


# --- device-side checks (independent of the code under test) ---------------


def _local_extremum(order, descending):
    """Bool grid: vertex is a strict local max (min) of the order field over
    the 6-stencil — computed from shifts, not through the kernels."""
    import jax.numpy as jnp
    from repro.core.steepest import neighbor_offsets, shift_fill
    key = order if descending else -order
    fill = jnp.iinfo(key.dtype).min
    ext = jnp.ones(order.shape, bool)
    for off in neighbor_offsets(order.ndim, 6):
        ext = ext & (key > shift_fill(key, off, fill))
    return ext


def check_manifold(labels, order, descending):
    """Failures (a list of names) of the manifold checks: every label is a
    root, and every label is a critical vertex of the right kind."""
    import jax
    import jax.numpy as jnp
    from repro.core._table import materialize

    @jax.jit
    def run(lab, order):
        flat = lab.ravel()
        n = flat.size
        in_range = jnp.all((flat >= 0) & (flat < n))
        safe, ext = materialize((jnp.clip(flat, 0, n - 1),
                                 _local_extremum(order, descending).ravel()))
        root = jnp.all(flat[safe] == flat)
        crit = jnp.all(ext[safe])
        return in_range, root, crit

    names = ("in_range", "root", "critical")
    return [k for k, ok in zip(names, run(labels, order)) if not bool(ok)]


def check_cc(labels, mask):
    """Failures of the cc checks: -1 exactly where unmasked; every label is
    a masked root no smaller than the vertex it labels."""
    import jax
    import jax.numpy as jnp
    from repro.core._table import materialize

    @jax.jit
    def run(lab, mask):
        flat, m = lab.ravel(), mask.ravel()
        n = flat.size
        unmasked = jnp.all((flat == -1) == ~m)
        in_range = jnp.all(jnp.where(m, (flat >= 0) & (flat < n), True))
        safe = materialize(jnp.clip(flat, 0, n - 1))
        root = jnp.all(jnp.where(m, flat[safe] == flat, True))
        masked_root = jnp.all(jnp.where(m, m[safe], True))
        largest = jnp.all(jnp.where(m, flat >= jnp.arange(n), True))
        return unmasked, in_range, root, masked_root, largest

    names = ("unmasked", "in_range", "root", "masked_root", "largest")
    return [k for k, ok in zip(names, run(labels, mask)) if not bool(ok)]


def same(a, b):
    """Bit-for-bit equality of two device arrays, wherever each lives."""
    import jax
    import jax.numpy as jnp
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    if a.sharding != b.sharding:
        b = jax.device_put(b, a.sharding)
    return bool(jnp.array_equal(a, b))


def require(failures, what):
    if failures:
        raise AssertionError(f"{what}: failed {failures}")
    log(f"[check] {what}: ok")


def label_count(labels):
    """Distinct labels == roots (every label is a root, checked above)."""
    import jax.numpy as jnp
    flat = labels.ravel()
    return int(jnp.sum(flat == jnp.arange(flat.size, dtype=flat.dtype)))


# --- phases -----------------------------------------------------------------


def make_inputs(clock, size, seed, tag):
    """Perlin field, order field and top-10% mask, all on the device."""
    import jax
    import jax.numpy as jnp
    from repro.core import compute_order
    from repro.data.perlin import perlin_noise_device

    with Phase(clock, f"generate_{tag}"):
        field = jax.block_until_ready(
            perlin_noise_device((size,) * 3, FREQUENCY, seed))
    with Phase(clock, f"order_{tag}"):
        order = jax.block_until_ready(compute_order(field))
    # threshold_quantile=0.9 (configs/dpc_grid.py): the value of rank
    # floor(0.9 (n-1)) — the order field is the rank, so no second sort
    n = order.size
    k = int(0.9 * (n - 1))
    q90 = field.ravel()[jnp.argmax(order.ravel() == k)]
    mask = field > q90
    log(f"[data] {tag}: shape {field.shape} q90 {float(q90)!r} "
        f"masked {int(jnp.sum(mask))} of {n}")
    return field, order, mask


def ms_request(order, backend, mesh=None, table_mode="replicated"):
    from repro.topology import TopologyRequest
    return TopologyRequest("ms", order=order, backend=backend, mesh=mesh,
                           table_mode=table_mode)


def cc_request(mask, backend, mesh=None, table_mode="replicated"):
    from repro.topology import TopologyRequest
    return TopologyRequest("cc", mask=mask, backend=backend, mesh=mesh,
                           table_mode=table_mode)


def submit(clock, name, req):
    import jax
    from repro.topology import submit as topo_submit
    with Phase(clock, name):
        res = topo_submit(req)
        jax.block_until_ready((res.labels, res.descending, res.ascending,
                               res.segmentation))
    return res


def log_stats(name, stats):
    keys = ("local_iters", "table_iters", "stitch_rounds", "comm_phases",
            "exchange_rounds", "converged")
    if stats is None:
        return
    parts = stats.items() if "descending" in stats else [("", stats)]
    for sub, st in parts:
        log(f"[stats] {name}{'/' + sub if sub else ''}: "
            + " ".join(f"{k}={st[k]}" for k in keys))


def ms_same(a, b):
    return all(same(getattr(a, f), getattr(b, f))
               for f in ("descending", "ascending", "segmentation"))


def check_ms(res, order, what):
    require(check_manifold(res.descending, order, True),
            f"{what} descending manifold")
    require(check_manifold(res.ascending, order, False),
            f"{what} ascending manifold")


def oracle_phase(clock, seed, size):
    """64^3: pure and distributed MS / CC against the numpy oracles."""
    import numpy as np
    from oracles import oracle_components, oracle_manifold
    from repro.core import make_dpc_mesh
    from repro.core.connected_components import connected_components_grid

    field, order, mask = make_inputs(clock, size, seed, str(size))
    mesh = make_dpc_mesh((1,))
    warm(clock, f"warm_compile_{size}", grid_jobs(mesh, order, mask)
         + [(connected_components_grid, (mask, 6))])
    with Phase(clock, f"oracle_numpy_{size}"):
        o = np.asarray(order)
        m = np.asarray(mask)
        want_desc = oracle_manifold(o, 6, descending=True)
        want_asc = oracle_manifold(o, 6, descending=False)
        want_cc = oracle_components(m, 6)
    for backend in ("pure", "distributed"):
        m_or_none = mesh if backend == "distributed" else None
        ms = submit(clock, f"ms_{backend}_{size}",
                    ms_request(order, backend, m_or_none))
        cc = submit(clock, f"cc_{backend}_{size}",
                    cc_request(mask, backend, m_or_none))
        ok = (np.array_equal(np.asarray(ms.descending), want_desc)
              and np.array_equal(np.asarray(ms.ascending), want_asc)
              and np.array_equal(np.asarray(cc.labels), want_cc))
        require([] if ok else ["oracle"],
                f"{size}^3 {backend} vs numpy oracles")


def kernel_dispatch_check(clock, order, mask):
    """The default dispatch compiles the Pallas kernel on this chip."""
    import jax
    from repro.kernels.ops import fused_local_phase
    with Phase(clock, "kernel_dispatch_compile"):
        for mode, x in (("manifold", order), ("cc", mask)):
            text = jax.jit(lambda f, m=mode: fused_local_phase(f, 6, m)) \
                .lower(x).compile().as_text()
            if "tpu_custom_call" not in text:
                raise AssertionError(f"{mode}: default dispatch did not "
                                     "compile the Pallas kernel")
    log("[check] default dispatch runs the compiled kernel: ok")


def one_chip(clock, seed, size, oracle_size):
    import jax
    import numpy as np
    from repro.core import make_dpc_mesh
    from repro.core.connected_components import connected_components_grid
    from repro.serve import TopologyEngine

    oracle_phase(clock, seed, oracle_size)
    field, order, mask = make_inputs(clock, size, seed, str(size))
    del field
    mesh = make_dpc_mesh((1,))
    warm(clock, f"warm_compile_{size}", grid_jobs(mesh, order, mask)
         + [(connected_components_grid, (mask, 6))])
    ms_d = submit(clock, f"ms_distributed_{size}",
                  ms_request(order, "distributed", mesh))
    log_stats("ms_distributed", ms_d.stats)
    check_ms(ms_d, order, f"{size}^3 distributed")
    ms_p = submit(clock, f"ms_pure_{size}", ms_request(order, "pure"))
    require([] if ms_same(ms_d, ms_p) else ["parity"],
            f"{size}^3 ms distributed == pure")
    del ms_p
    counts = (label_count(ms_d.descending), label_count(ms_d.ascending))
    # keep the facade's MS labels on the host for the engine comparison:
    # the pure cc program below needs ~10 GB of device temporaries
    host = {f: np.asarray(getattr(ms_d, f))
            for f in ("descending", "ascending", "segmentation")}
    del ms_d
    cc_d = submit(clock, f"cc_distributed_{size}",
                  cc_request(mask, "distributed", mesh))
    log_stats("cc_distributed", cc_d.stats)
    require(check_cc(cc_d.labels, mask), f"{size}^3 distributed cc")
    cc_p = submit(clock, f"cc_pure_{size}", cc_request(mask, "pure"))
    require([] if same(cc_d.labels, cc_p.labels) else ["parity"],
            f"{size}^3 cc distributed == pure")
    del cc_p
    log(f"[labels] {size}^3: maxima {counts[0]} minima {counts[1]} "
        f"components {label_count(cc_d.labels)}")

    # the serving path: both queries in one engine batch
    host["labels"] = np.asarray(cc_d.labels)
    del cc_d
    eng = TopologyEngine()
    with Phase(clock, f"engine_ms_cc_{size}"):
        ms_e, cc_e = eng.submit_batch([ms_request(order, "distributed", mesh),
                                       cc_request(mask, "distributed", mesh)])
    ok = (all(np.array_equal(np.asarray(getattr(ms_e, f)), host[f])
              for f in ("descending", "ascending", "segmentation"))
          and np.array_equal(np.asarray(cc_e.labels), host["labels"]))
    require([] if ok else ["parity"], f"{size}^3 engine == facade")
    if jax.devices()[0].platform == "tpu":
        kernel_dispatch_check(clock, order, mask)


def four_chips(clock, seed, size):
    import jax
    from repro.core import make_dpc_mesh
    from repro.core.connected_components import connected_components_grid

    devs = jax.devices()
    if len(devs) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devs)}")
    with jax.default_device(devs[0]):
        field, order, mask = make_inputs(clock, size, seed, str(size))
        del field
        warm(clock, f"warm_compile_pure_{size}",
             [(connected_components_grid, (mask, 6))])
        ms_p = submit(clock, f"ms_pure_{size}", ms_request(order, "pure"))
        check_ms(ms_p, order, f"{size}^3 pure")
        cc_p = submit(clock, f"cc_pure_{size}", cc_request(mask, "pure"))
        require(check_cc(cc_p.labels, mask), f"{size}^3 pure cc")
    meshes = [make_dpc_mesh(layout, devices=devs[:4]) for layout in LAYOUTS_4]
    warm(clock, f"warm_compile_{size}",
         [job for mesh in meshes
          for job in grid_jobs(mesh, order, mask, TABLE_MODES)])
    for layout, mesh in zip(LAYOUTS_4, meshes):
        for tm in TABLE_MODES:
            tag = f"{'x'.join(map(str, layout))}_{tm}"
            ms_d = submit(clock, f"ms_distributed_{tag}",
                          ms_request(order, "distributed", mesh, tm))
            log_stats(f"ms_{tag}", ms_d.stats)
            require([] if ms_same(ms_d, ms_p) else ["parity"],
                    f"ms {tag} == one-device pure")
            del ms_d
            cc_d = submit(clock, f"cc_distributed_{tag}",
                          cc_request(mask, "distributed", mesh, tm))
            log_stats(f"cc_{tag}", cc_d.stats)
            require([] if same(cc_d.labels, cc_p.labels) else ["parity"],
                    f"cc {tag} == one-device pure")
            del cc_d
            log_memory(devs[:4])


def log_memory(devices):
    for d in devices:
        st = d.memory_stats() or {}
        log(f"[memory] {d}: peak_bytes_in_use "
            f"{st.get('peak_bytes_in_use', 'not reported')}")


def run(chips=1, seed=0, size=SIZE, oracle_size=ORACLE_SIZE,
        require_tpu=True):
    """The smoke; returns the result dict of the last line.  Tests call it
    at a tiny size with ``require_tpu=False``."""
    import jax

    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform}); "
                         "nothing was run")
    from repro.launch.jax_cache import enable_compile_cache
    cache = enable_compile_cache()
    log(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if chips == 4:
        four_chips(clock, seed, size)
        count = 4
    else:
        one_chip(clock, seed, size, oracle_size)
        count = 1
    log(f"[time] total: {time.perf_counter() - t0:.3f}s "
        f"(xla_compile {clock.total:.3f}s)")
    log_memory(jax.devices()[:count])
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind, "count": count}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    result = run(chips=args.chips, seed=args.seed)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
