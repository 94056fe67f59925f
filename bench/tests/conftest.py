"""Shared helpers of the benchmark's CPU tests.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

A run of a cell happens in a subprocess (four virtual CPU devices need
`XLA_FLAGS` before JAX is imported), in a copy of the benchmark made in a
temporary directory: `bench/` and `BENCHMARK.json` copied, `src/` linked,
and tiny configurations added beside the real ones, so that the real
files are never edited.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
TINY = {"tiny1": ([24, 20, 16], [1]), "tiny4": ([24, 20, 16], [2, 2]),
        "tiny32": ([32, 32, 32], [1])}
TRAFFIC = ("ms_from_field", "cc_top10")


def add_tiny_cells(root: Path):
    """Tiny configurations (the real ones at another grid and layout)
    and one cell for each of them under each traffic mix."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    base = json.loads((root / "bench/configs/perlin512.json").read_text())
    for name, (grid, layout) in TINY.items():
        cfg = dict(base, grid=grid, layout=layout)
        (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": ["grid"], "why": "test"})
        for t in TRAFFIC:
            spec["workloads"].append({
                "name": f"{name}.{t}", "config": name, "traffic": t,
                "chips": 4 if len(layout) > 1 else 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))


def make_checkout(dest: Path) -> Path:
    dest.mkdir(parents=True, exist_ok=True)
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    (dest / "src").symlink_to(REPO / "src")
    return dest


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = make_checkout(tmp_path_factory.mktemp("checkout"))
    add_tiny_cells(root)
    return root


def run_python(root: Path, code: str, devices: int = 4, timeout=600):
    """Run `code` in a subprocess on `devices` virtual CPU devices, with
    the checkout's `bench/` importable; returns the CompletedProcess."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    prelude = f"import sys\nsys.path.insert(0, {str(root / 'bench')!r})\n"
    if (root / "src").exists():
        prelude += f"sys.path.insert(0, {str(root / 'src')!r})\n"
    return subprocess.run([sys.executable, "-c",
                           prelude + textwrap.dedent(code)],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=timeout)


def run_cell(root: Path, workload: str, seed=2**31 + 11, seconds=1,
             trace=0, patch: str = ""):
    """One run of `workload` with the look for a chip skipped, after
    `patch` (code that breaks the timed path underneath, or nothing);
    returns (result of the last stdout line or None, CompletedProcess)."""
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    proc = run_python(root, patch + f"\nimport run\n"
                      f"run.run({argv!r}, require_tpu=False)\n")
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if proc.returncode == 0 and lines \
        else None
    return result, proc
