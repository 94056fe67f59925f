"""Reduction of a JAX profiler trace (`*.xplane.pb`) to device metrics.

* busy: the union of the intervals in which an operation ran on a chip
  (the `XLA Ops` line of its `/device:TPU:<id>` plane), inside the window;
* window: the host span named ``window`` that the harness puts around the
  measured loop (host and device events share the trace's clock);
* op time: self time of each device operation (nested events are
  subtracted from their parent), so kernel and collective times sum without
  double counting;
* breakdown: the operations that took most time, and the idle gaps of each
  chip, named by the harness span and the innermost host event that was
  running at the gap's midpoint.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
HARNESS_SPANS = ("order", "mask", "submit.ms", "submit.cc")
TOP = 10


# an HLO array type in a TPU op's text: dtype[dims]{layout}; memory space
# S(1) in the layout is the core's on-chip memory, no S(n) is HBM
_TYPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f(?:16|32|64))"
                   r"\[([\d,]*)\](?:\{([^}]*)\})?")
_OPERAND = re.compile(_TYPE.pattern + r"\s+(%[\w.\-]+)")
# result type (one array, or a tuple whose layouts hold parentheses), the
# opcode, then its operands
_OP = re.compile(r"(\((?:[^()]|\([^()]*\))*\)|\S+)\s+([a-z][\w\-]*)\((.*)$",
                 re.S)
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
             "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}


def _buffer(m):
    dtype, dims, layout = m.group(1), m.group(2), m.group(3) or ""
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    space = re.search(r"S\((\d+)\)", layout)
    return n * _ITEMSIZE[dtype], int(space.group(1)) if space else 0


def hbm_bytes(op_text: str) -> int:
    """Bytes that an op's distinct operands and its results occupy in HBM
    (memory space 0), from the HLO text that names its trace event."""
    m = _OP.match(op_text.partition(" = ")[2])
    if m is None:
        return 0
    head, tail = m.group(1), m.group(3)
    results = [_buffer(m) for m in _TYPE.finditer(head)]
    operands = {m.group(4): _buffer(m) for m in _OPERAND.finditer(tail)}
    return sum(b for b, space in results + list(operands.values())
               if space == 0)


def short_name(op_text: str) -> str:
    """`%fusion.3 = s32[8,128]{...} fusion(...)` -> `fusion.3 s32[8,128]`."""
    name, _, rest = op_text.partition(" = ")
    m = _TYPE.search(rest)
    shape = f" {m.group(1)}[{m.group(2)}]" if m else ""
    return name.lstrip("%") + shape


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """(name, start, end, self_ns) of events on one line, where an event
    that lies inside another is subtracted from that one's self time."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for name, s, e in evs:
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack and e <= stack[-1][2]:
            stack[-1][3] -= e - s
        stack.append([name, s, e, e - s])
    out.extend(tuple(x) for x in reversed(stack))
    return out


class Reduced:
    """One traced window: per-chip device operations and the host events
    on the harness's thread.  Times are in seconds."""

    def __init__(self, window, device_ops, host_events):
        self.t0, self.t1 = window
        self.window_s = (self.t1 - self.t0) * 1e-9
        # device id -> [(name, start, end, self_ns)], clipped to the window
        self.ops = {}
        for dev, evs in device_ops.items():
            clipped = [(n, max(s, self.t0), min(e, self.t1))
                       for n, s, e in evs if e > self.t0 and s < self.t1]
            self.ops[dev] = _self_times(clipped)
        self.host = host_events       # [(name, start, end)]
        self.busy = {dev: _union((s, e) for _, s, e, _ in evs)
                     for dev, evs in self.ops.items()}
        busy = [sum(e - s for s, e in iv) * 1e-9
                for iv in self.busy.values()]
        self.busy_s = sum(busy) / len(busy) if busy else 0.0

    def op_seconds(self, pattern) -> dict:
        """Per chip: seconds of self time of the operations whose name
        matches `pattern` (a regular expression, searched)."""
        rx = re.compile(pattern)
        return {dev: sum(t for n, _, _, t in evs if rx.search(n)) * 1e-9
                for dev, evs in self.ops.items()}

    def op_events(self, pattern) -> list:
        """(name, seconds) of every matching operation, on every chip."""
        rx = re.compile(pattern)
        return [(n, t * 1e-9) for evs in self.ops.values()
                for n, _, _, t in evs if rx.search(n)]

    def _host_state(self, t):
        """Name of what the harness's thread was doing at time `t`: its
        span, and the innermost host event inside it."""
        covering = [(e - s, n) for n, s, e in self.host
                    if s <= t < e and n != WINDOW]
        span = next((n for _, n in sorted(covering, reverse=True)
                     if n in HARNESS_SPANS), None)
        if span is None:
            return "between queries"
        inner = min((d, n) for d, n in covering)[1]
        return span if inner == span else f"{span}: {inner}"

    def idle_gaps(self) -> dict:
        """Idle seconds per chip, averaged over chips, by host state."""
        total = defaultdict(float)
        for iv in self.busy.values():
            edges = [self.t0] + [x for s, e in iv for x in (s, e)] + [self.t1]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    total[self._host_state((s + e) / 2)] += (e - s) * 1e-9
        n = max(len(self.busy), 1)
        return {k: v / n for k, v in total.items()}

    def breakdown(self) -> dict:
        per_op = defaultdict(float)
        for evs in self.ops.values():
            for n, _, _, t in evs:
                per_op[short_name(n)] += t * 1e-9
        n = max(len(self.ops), 1)
        ops = sorted(((k, v / n) for k, v in per_op.items()),
                     key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [list(x) for x in ops],
                "idle_gaps": [list(x) for x in gaps]}


def reduce_profile(pd, device_ids) -> Reduced:
    """Reduce a `jax.profiler.ProfileData` to the chips in `device_ids`."""
    device_ops, host, window = {}, [], None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events]
            device_ops[int(m.group(1))] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.end_ns))
                       for e in line.events]
                win = [x for x in evs if x[0] == WINDOW]
                if win:
                    window, host = (win[0][1], win[0][2]), evs
    if window is None:
        raise ValueError("the trace has no host span named 'window'")
    return Reduced(window, device_ops, host)


def reduce_dir(trace_dir, devices) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(find_xplane(trace_dir)))
    return reduce_profile(pd, {d.id for d in devices})
