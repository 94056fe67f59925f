"""The trace reduction (busy union, idle share, op self times, kernel
and collective time, breakdown) on hand-made traces and on two traced runs
of the harness recorded on a TPU v5e chip (`data/`: MS at 320^3, cc at
256^3), and the peaks table."""
import importlib.util
import sys
from pathlib import Path

import pytest

from conftest import REPO

sys.path.insert(0, str(REPO / "bench"))

import devtrace  # noqa: E402
import peaks  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
FIXTURES = sorted(DATA.glob("*.xplane.pb"))


def metric(name):
    path = REPO / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Ctx:
    def __init__(self, trace, n_queries=1, peak=None):
        self.trace, self.n_queries = trace, n_queries
        self.peak = peak or peaks.lookup("TPU v5 lite")
        self.spans, self.stats = {}, []


KERNEL_HBM = ("%fused_local_phase_manifold.1 = s32[10,8,128]{2,1,0:T(8,128)}"
              " custom-call(s32[10,8,128]{2,1,0:T(8,128)} %p, "
              "s32[10,8,128]{2,1,0:T(8,128)} %p), custom_call_target="
              "\"tpu_custom_call\", operand_layout_constraints="
              "{s32[10,8,128]{2,1,0}}")
KERNEL_VMEM = KERNEL_HBM.replace("T(8,128)}", "T(8,128)S(1)}")
WHILE = ("%while.3 = (s32[64]{0:T(128)}, pred[]{:T(512)}) while((s32[64]"
         "{0:T(128)}, pred[]{:T(512)}) %tuple.1), condition=%c, body=%b")
GATHER = "%all-gather.2 = s32[4,64]{1,0} all-gather(s32[1,64]{1,0} %x)"


def hand_made():
    ops = {0: [(KERNEL_HBM, 10, 30), (WHILE, 40, 90), (GATHER, 50, 60)],
           1: [(KERNEL_HBM, 10, 20), (GATHER, 120, 130)]}
    host = [("window", 0, 100), ("order", 5, 35), ("submit.ms", 35, 100),
            ("$_table.py:231 check_converged", 82, 100)]
    return devtrace.Reduced((0, 100), ops, host)


def test_busy_idle_and_self_times():
    r = hand_made()
    assert r.window_s == pytest.approx(100e-9)
    # chip 0: [10, 30] and [40, 90]; chip 1: [10, 20] (the gather after
    # the window is clipped away)
    assert r.busy_s == pytest.approx((70e-9 + 10e-9) / 2)
    assert r.op_seconds("all-gather") == {0: pytest.approx(10e-9), 1: 0.0}
    # the while loop's self time leaves out the gather nested in it
    assert r.op_seconds(r"^%while")[0] == pytest.approx(40e-9)
    gaps = r.idle_gaps()
    assert sum(gaps.values()) == pytest.approx(100e-9 - r.busy_s)
    assert gaps["order"] == pytest.approx((10 + 10) * 1e-9 / 2)
    assert "submit.ms: $_table.py:231 check_converged" in gaps
    b = r.breakdown()
    assert b["device_ops"][0][0] == "while.3 s32[64]"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_hbm_bytes_from_hlo_text():
    assert devtrace.hbm_bytes(KERNEL_HBM) == 2 * 10 * 8 * 128 * 4
    assert devtrace.hbm_bytes(KERNEL_VMEM) == 0
    assert devtrace.hbm_bytes(WHILE) == 64 * 4 + 1
    assert devtrace.short_name(GATHER) == "all-gather.2 s32[4,64]"


def test_metric_readers_on_hand_made_trace():
    r = hand_made()
    ctx = Ctx(r, n_queries=2)
    roof = metric("fused_local_phase_roofline")(ctx)
    least = 2 * 10 * 8 * 128 * 4 / 819e9
    assert roof == pytest.approx(100 * 2 * least / 30e-9)
    idle = metric("device_idle_pct")(ctx)
    assert idle == pytest.approx(100 * (1 - r.busy_s / r.window_s))
    vmem = devtrace.Reduced((0, 100), {0: [(KERNEL_VMEM, 0, 50)]}, [])
    assert metric("fused_local_phase_roofline")(Ctx(vmem)) is None


def test_peaks_table():
    p = peaks.lookup("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v4")


@pytest.mark.skipif(not FIXTURES, reason="no recorded trace")
@pytest.mark.parametrize("path", FIXTURES, ids=[p.stem for p in FIXTURES])
def test_recorded_tpu_trace(path):
    from jax.profiler import ProfileData
    r = devtrace.reduce_profile(ProfileData.from_file(str(path)), {0})
    assert 0 < r.busy_s < r.window_s
    assert list(r.ops) == [0]
    kernel = r.op_events(r"^%fused_local_phase_")
    assert kernel and all(t > 0 for _, t in kernel)
    b = r.breakdown()
    assert b["device_ops"] and b["idle_gaps"]
    assert sum(v for _, v in b["idle_gaps"]) <= r.window_s - r.busy_s + 1e-9
    assert all(len(n) < 200 for n, _ in b["device_ops"])
    idle = metric("device_idle_pct")(Ctx(r))
    assert 0 < idle < 100
    # the init kernel's buffers of these cells are in HBM: a share of its
    # roofline is read, and no share passes 100%
    roof = metric("fused_local_phase_roofline")(Ctx(r))
    assert 0 < roof <= 100