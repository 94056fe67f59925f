"""The weak-scaled cell `perlin_weak_2x2.cc_top10`, from its own files: its
entries in `BENCHMARK.json` run as they are, on four virtual devices, with
the configuration's grid shrunk in a copy; its exchange readers read a
hand-made trace; and every file of the benchmark that was there stays as
it was."""
import json
import sys

import pytest

from conftest import REPO, make_checkout, run_cell
from test_correctness import ALTER_ANSWER
from test_data_driven import digest

sys.path.insert(0, str(REPO / "bench"))

import devtrace  # noqa: E402
import layers  # noqa: E402
from test_devtrace import Ctx, metric  # noqa: E402

CELL = "perlin_weak_2x2.cc_top10"
CONFIG = "bench/configs/perlin_weak_2x2.json"
GRID = [24, 20, 16]                     # blocks of 12 x 10 x 16
# the gathered table's in-domain slots: both faces of each block along x
# (20 x 16 each) and along y (24 x 16 each); a label of 4 bytes and a
# mask of 1 each
TABLE_BYTES = (4 * 20 * 16 + 4 * 24 * 16) * 5
NEW = ("exchange_s.cc2x2", "table_iters.cc2x2", "exchange_mib.cc2x2")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark whose `perlin_weak_2x2` entry names a copy
    of its configuration at a small grid; every other file as it is."""
    root = make_checkout(tmp_path_factory.mktemp("weak2x2"))
    before = digest(root)
    small = json.loads((root / CONFIG).read_text())
    small["grid"] = GRID
    (root / "bench/configs/weak_2x2_small.json").write_text(
        json.dumps(small))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"]
                 if c["name"] == "perlin_weak_2x2")
    entry["file"] = "bench/configs/weak_2x2_small.json"
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, before


def test_configuration_and_entries():
    """The configuration is perlin512's but for the grid, the layout and
    what it says of itself; the cell and its metrics are as declared."""
    cfg = json.loads((REPO / CONFIG).read_text())
    base = json.loads((REPO / "bench/configs/perlin512.json").read_text())
    assert cfg["grid"] == [1024, 1024, 384] and cfg["layout"] == [2, 2]
    own = {"grid", "layout", "source", "deployment", "reduced", "assumed",
           "guarantees"}
    assert {k: v for k, v in cfg.items() if k not in own} \
        == {k: v for k, v in base.items() if k not in own}
    assert set(cfg["reduced"]) == {"grid"}
    assert {"layout", "table_mode", "connectivity", "origin"} \
        <= set(cfg["assumed"])
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("perlin_weak_2x2", "cc_top10", 4)
    applies = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]
               if CELL in m.get("workloads", [CELL])]
    assert set(applies) == {"cc_query_s", "peak_hbm_gib", "setup_s", *NEW}


def test_plain_run_is_correct_on_four_devices(checkout):
    root, _ = checkout
    res, proc = run_cell(root, CELL)
    assert res is not None, proc.stderr[-3000:]
    assert res["correct"] and res["device"]["count"] == 4
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert set(res["metrics"]) == {"cc_query_s", "peak_hbm_gib", "setup_s"}
    assert res["metrics"]["cc_query_s"]["value"] > 0


def test_traced_run_reads_the_table_counters(checkout):
    """The counters come from the program; the CPU trace has no TPU
    plane, so `exchange_s.cc2x2` reads nothing here and the line leaves it
    out (its reader is checked on a hand-made trace below)."""
    root, _ = checkout
    res, proc = run_cell(root, CELL, trace=1)
    assert res is not None, proc.stderr[-3000:]
    assert res["correct"] and res["device"]["count"] == 4
    m = res["metrics"]
    assert m["table_iters.cc2x2"]["value"] >= 1
    assert m["exchange_mib.cc2x2"]["value"] == TABLE_BYTES / 2**20
    assert "exchange_s.cc2x2" not in m


def test_planted_label_fault_is_not_correct(checkout):
    root, _ = checkout
    res, proc = run_cell(root, CELL, patch=ALTER_ANSWER)
    assert res is not None, proc.stderr[-3000:]
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] > 0
    assert res["checks"]["labels_mismatch"]["value"] > 0


EXCHANGE = "jit(run)/shard_map/dpc.table.gather/dpc.exchange/all_gather:"
PATHS = {0: {"%all-gather-start.1": EXCHANGE,
             "%all-gather-done.1": EXCHANGE,
             "%fusion.2": "jit(run)/shard_map/dpc.table.chase/gather:"},
         1: {"%all-gather-start.1": EXCHANGE,
             "%all-gather-done.1": EXCHANGE}}


def hand_made():
    """Chip 0: the gather's start [0, 5] and done [5, 25], then a chase
    [25, 40]; chip 1: start [0, 5], done [5, 45] (it waits longer)."""
    ops = {0: [("%all-gather-start.1", 0, 5), ("%all-gather-done.1", 5, 25),
               ("%fusion.2", 25, 40)],
           1: [("%all-gather-start.1", 0, 5), ("%all-gather-done.1", 5, 45)]}
    return devtrace.Reduced((0, 100), ops, [("window", 0, 100)])


def test_exchange_reader_on_hand_made_trace(monkeypatch):
    read = metric("exchange_s")
    monkeypatch.setattr(layers, "op_paths", lambda trace_dir=None: PATHS)
    # start and done, waiting included, averaged over the two chips
    assert read(Ctx(hand_made(), n_queries=2)) \
        == pytest.approx((25 + 45) / 2 / 2 * 1e-9)
    # a program that names no exchange (one that predates the scope)
    # reads nothing, where the layer split has other layers
    older = {d: {k: v.replace("/dpc.exchange", "") for k, v in p.items()}
             for d, p in PATHS.items()}
    monkeypatch.setattr(layers, "op_paths", lambda trace_dir=None: older)
    assert read(Ctx(hand_made())) is None
    assert read(Ctx(None)) is None


def test_every_file_that_was_there_is_unchanged(checkout):
    """Last in the file: after the runs above, every file of the copy's
    `bench/` that the repository has is byte for byte as it was."""
    root, before = checkout
    after = digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
