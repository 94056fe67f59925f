"""Device seconds by layer of the program, from the traced window.

The program names its layers with `jax.named_scope("dpc.<layer>[.<part>]")`
(`src/repro`), and the compiler keeps that path in each device op's
`tf_op` (`bench/xspace.py`).  An op's layer is the innermost path component
that starts with `dpc.`, without the prefix, up to its first dot:
`.../dpc.table.chase/while/body/gather:` is `table`, and the doubling
inside the cc stitch's loop is `doubling`.  An op under no such scope is
unscoped (None): the harness's own programs, and whatever the program
leaves unnamed.

Times are the window-clipped self times of `devtrace.Reduced.ops`, summed
per chip and averaged over the cell's chips, like `busy_s`.

    python3 bench/layers.py [trace dir]    # the split of the last traced run
"""
from __future__ import annotations

import functools
import re
import sys
from collections import defaultdict

import devtrace
import xspace

_LAYER = re.compile(r"(?:^|/)dpc\.(\w+)")


def layer_of(tf_op: str | None) -> str | None:
    found = _LAYER.findall(tf_op or "")
    return found[-1] if found else None


@functools.lru_cache(maxsize=4)
def _tf_ops(path: str, mtime_ns: int) -> dict:
    return xspace.tf_ops(path)


def op_paths(trace_dir=None) -> dict:
    """{device id: {op text: tf_op}} of the traced run's `.xplane.pb`
    (`run.TRACE_DIR` by default), read once per file."""
    if trace_dir is None:
        from run import TRACE_DIR as trace_dir
    path = devtrace.find_xplane(trace_dir)
    return _tf_ops(str(path), path.stat().st_mtime_ns)


def split(trace, paths) -> dict:
    """{layer or None: seconds of self time}, averaged over chips."""
    total = defaultdict(float)
    for dev, evs in trace.ops.items():
        ops = paths.get(dev, {})
        for name, _, _, self_ns in evs:
            total[layer_of(ops.get(name))] += self_ns * 1e-9
    n = max(len(trace.ops), 1)
    return {k: v / n for k, v in total.items()}


def layer_seconds(ctx, layer):
    """Device seconds per query of `layer` in the window (0.0 where it did
    not run); None without a trace, and where no op of the window is under
    a `dpc.` scope (a program that names no layer)."""
    if ctx.trace is None or not ctx.n_queries:
        return None
    s = split(ctx.trace, op_paths())
    if all(k is None for k in s):
        return None
    return s.get(layer, 0.0) / ctx.n_queries


def main(argv):
    """Print the per-layer split of a trace and its 20 costliest ops."""
    from jax.profiler import ProfileData
    trace_dir = argv[1] if len(argv) > 1 else None
    if trace_dir is None:
        from run import TRACE_DIR as trace_dir
    paths = op_paths(trace_dir)
    trace = devtrace.reduce_profile(
        ProfileData.from_file(str(devtrace.find_xplane(trace_dir))),
        set(paths))
    s = split(trace, paths)
    print(f"window {trace.window_s:.6f} s, busy {trace.busy_s:.6f} s, "
          f"self time {sum(s.values()):.6f} s")
    for k, v in sorted(s.items(), key=lambda kv: -kv[1]):
        print(f"{k or '(unscoped)':>12} {v:12.6f} s")
    top = defaultdict(float)
    for dev, evs in trace.ops.items():
        for name, _, _, t in evs:
            key = (devtrace.short_name(name), paths[dev].get(name, ""))
            top[key] += t * 1e-9 / len(trace.ops)
    for (name, tf), v in sorted(top.items(), key=lambda kv: -kv[1])[:20]:
        print(f"{v:12.6f} s  {layer_of(tf) or '-':>10}  {name}  {tf}")


if __name__ == "__main__":
    main(sys.argv)
