"""Device seconds by layer: the `tf_op` walk of a recorded chip trace
(`xspace.py`), the layer rule and self times (`layers.py`) on hand-made
traces, each new reader, and the host reads counted in a traced CPU run."""
import json
import sys

import pytest

from conftest import REPO, add_tiny_cells, make_checkout, run_cell

sys.path.insert(0, str(REPO / "bench"))

import devtrace  # noqa: E402
import layers  # noqa: E402
import xspace  # noqa: E402
from test_devtrace import DATA, Ctx, metric  # noqa: E402

CC256 = DATA / "perlin256.cc_top10.xplane.pb"
NEW = ("doubling_s", "stitch_s", "table_s", "order_device_s")


def test_tf_ops_of_a_recorded_chip_trace():
    paths = xspace.tf_ops(CC256)
    assert list(paths) == [0]
    ops = paths[0]
    gather = next(t for t in ops if t.startswith("%fusion.262 = "))
    assert ops[gather] == "jit(run)/while/body/gather:"
    kernel = next(t for t in ops if t.startswith("%fused_local_phase_cc"))
    assert "fused_local_phase_cc/pallas_call" in ops[kernel]


def test_a_trace_with_no_scopes(monkeypatch):
    """The recorded trace predates the scopes: every op is unscoped, the
    share reads 0, and the layer readers read nothing."""
    from jax.profiler import ProfileData
    paths = xspace.tf_ops(CC256)
    monkeypatch.setattr(layers, "op_paths", lambda trace_dir=None: paths)
    r = devtrace.reduce_profile(ProfileData.from_file(str(CC256)), {0})
    assert set(layers.split(r, paths)) == {None}
    assert metric("scoped_pct")(Ctx(r)) == 0
    for name in NEW:
        assert metric(name)(Ctx(r)) is None
    assert metric("host_reads")(Ctx(r)) is None


@pytest.mark.parametrize("tf_op, layer", [
    ("jit(run)/dpc.table.chase/while/body/gather:", "table"),
    ("jit(run)/dpc.cc_stitch/while/body/dpc.doubling/while/body/gather:",
     "doubling"),
    ("jit(run)/dpc.cc_stitch/while/body/scatter-max:", "cc_stitch"),
    ("jit(compute_order)/dpc.order/sort:", "order"),
    ("jit(run)/dpc.table/dpc.table.gather/concatenate:", "table"),
    ("jit(_fingerprints)/mul:", None),
    ("jit(run)/notdpc.x/add:", None),
    ("", None),
    (None, None),
])
def test_layer_rule(tf_op, layer):
    assert layers.layer_of(tf_op) == layer


STITCH = "jit(run)/dpc.cc_stitch/while:"
DOUBLING = "jit(run)/dpc.cc_stitch/while/body/dpc.doubling/while/body/gather:"
PATHS = {0: {"%while.1": STITCH, "%gather.2": DOUBLING,
             "%chase.3": "jit(run)/dpc.table.chase/while/body/gather:",
             "%sort.4": "jit(compute_order)/dpc.order/sort:",
             "%fp.5": "jit(_fingerprints)/reduce:"},
         1: {"%while.1": STITCH, "%gather.2": DOUBLING}}


def hand_made():
    """Chip 0: the stitch loop [0, 60] holds a doubling gather [10, 40];
    then a chase, an order sort, a harness op (a path with no scope) and
    an op with no path at all.  Chip 1: the loop alone.  Window [0, 100];
    eleven host reads inside it, one after it."""
    ops = {0: [("%while.1", 0, 60), ("%gather.2", 10, 40),
               ("%chase.3", 60, 70), ("%sort.4", 70, 80),
               ("%fp.5", 80, 90), ("%copy.6", 90, 95)],
           1: [("%while.1", 0, 50)]}
    host = [("window", 0, 100), ("topology.submit", 2, 90)] + [
        ("dpc.host_read", 50 + i, 51 + i) for i in range(11)] + [
        ("dpc.host_read", 101, 102)]          # after the window
    return devtrace.Reduced((0, 100), ops, host)


def test_split_takes_self_times_by_innermost_scope():
    s = layers.split(hand_made(), PATHS)
    ns = 1e-9
    # chip 0: stitch 60 - 30 nested, doubling 30, table 10, order 10,
    # unscoped 10 + 5; chip 1: stitch 50; averaged over the two chips
    assert s["cc_stitch"] == pytest.approx((30 + 50) / 2 * ns)
    assert s["doubling"] == pytest.approx(30 / 2 * ns)
    assert s["table"] == pytest.approx(10 / 2 * ns)
    assert s["order"] == pytest.approx(10 / 2 * ns)
    assert s[None] == pytest.approx(15 / 2 * ns)
    r = hand_made()
    assert sum(s.values()) == pytest.approx(r.busy_s)


def test_layer_readers_on_hand_made_trace(monkeypatch):
    monkeypatch.setattr(layers, "op_paths", lambda trace_dir=None: PATHS)
    ns = 1e-9
    ctx = Ctx(hand_made(), n_queries=2)
    assert metric("doubling_s")(ctx) == pytest.approx(15 / 2 * ns)
    assert metric("stitch_s")(ctx) == pytest.approx(40 / 2 * ns)
    assert metric("table_s")(ctx) == pytest.approx(5 / 2 * ns)
    assert metric("order_device_s")(ctx) == pytest.approx(5 / 2 * ns)
    assert metric("scoped_pct")(ctx) == pytest.approx(100 * 65 / 72.5)
    assert metric("host_reads")(ctx) == 11 / 2
    # a layer the window never ran reads 0, not nothing
    monkeypatch.setattr(layers, "op_paths",
                        lambda trace_dir=None: {0: {}, 1: PATHS[1]})
    assert metric("table_s")(ctx) == 0.0
    assert metric("host_reads")(Ctx(None)) is None
    for name in NEW:
        assert metric(name)(Ctx(None)) is None


@pytest.mark.parametrize("traffic, reads", [("cc_top10", 11),
                                            ("ms_from_field", 22)])
def test_traced_cpu_run_counts_host_reads(tmp_path, traffic, reads):
    """One read per `check_converged` and per `DPCStats` field, for each
    grid program of the query; the CPU trace has no TPU plane, so the
    device readers read nothing and the line leaves them out."""
    root = make_checkout(tmp_path / "checkout")
    add_tiny_cells(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell, real = f"tiny1.{traffic}", f"perlin512.{traffic}"
    for m in spec["per_layer"]:
        if real in m.get("workloads", ()):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    result, proc = run_cell(root, cell, trace=1)
    assert result is not None, proc.stderr[-3000:]
    kind = "cc" if traffic == "cc_top10" else "ms"
    assert result["metrics"][f"host_reads.{kind}"]["value"] == reads
    assert f"doubling_s.{kind}" not in result["metrics"]
    assert f"scoped_pct.{kind}" not in result["metrics"]
