"""A new cell needs only new files and entries: a configuration, a traffic
mix and a per-layer metric are added to a copy of the benchmark, and the
new cell runs end to end on four virtual devices while every file that was
there stays as it was."""
import hashlib
import json

from conftest import make_checkout, run_cell


def digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_from_files_alone(tmp_path):
    root = make_checkout(tmp_path / "checkout")
    before = digest(root)

    (root / "bench/configs/grid_2x2_small.json").write_text(json.dumps({
        "source": "test", "grid": [16, 12, 10], "origin": [5, 0, 9],
        "frequency": 0.1, "connectivity": 6, "layout": [2, 2],
        "table_mode": "replicated", "field_dtype": "float32"}))
    (root / "bench/traffic/cc_top50.json").write_text(json.dumps({
        "query": "cc", "top_fraction": 0.5}))
    (root / "bench/metrics/queries_per_s.py").write_text(
        "def read(ctx):\n"
        "    return ctx.n_queries / ctx.window_s\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = "grid_2x2_small.cc_top50"
    spec["configs"].append({"name": "grid_2x2_small", "source": "test",
                            "file": "bench/configs/grid_2x2_small.json",
                            "reduced": ["grid"], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "grid_2x2_small",
                              "traffic": "cc_top50", "chips": 4,
                              "why": "test"})
    spec["end_to_end"].append({"name": "cc50_query_s", "unit": "s",
                               "better": "lower", "bound": 0.05,
                               "source": "host_clock", "workloads": [cell]})
    for name in ("queries_per_s", "local_iters.cc50"):
        spec["per_layer"].append({
            "name": name, "unit": "1", "better": "higher",
            "source": "host_clock", "layer": "device",
            "moves": "cc50_query_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    plain, proc = run_cell(root, cell)
    assert plain is not None, proc.stderr[-3000:]
    assert plain["correct"] and plain["device"]["count"] == 4
    assert set(plain["checks"]) == {"labels_mismatch", "answers_wrong"}
    assert set(plain["metrics"]) == {"cc50_query_s", "peak_hbm_gib",
                                     "setup_s"}
    assert plain["metrics"]["cc50_query_s"]["value"] > 0

    traced, proc = run_cell(root, cell, trace=1)
    assert traced is not None, proc.stderr[-3000:]
    assert traced["correct"]
    assert traced["metrics"]["queries_per_s"]["value"] > 0
    assert traced["metrics"]["local_iters.cc50"]["value"] > 0
    assert "busy_s" in traced["device"] and "window_s" in traced["device"]

    after = digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
