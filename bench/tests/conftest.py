"""Shared set-up of the benchmark's own tests (CPU only).

``tiny`` copies the benchmark's files (``BENCHMARK.json`` and the
configurations, mixes, drivers, problems, metrics and peaks under
``bench/``) into a temporary checkout and shrinks every configuration, so that a whole run
of a cell takes seconds on the CPU.  Nothing here touches a TPU.
"""
import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
TINY = {
    "helmholtz-cyl": lambda c: c["mesh"].update(n_axial=8, n_cross=2),
}


def copy_bench(dst: Path) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for sub in ("configs", "traffic", "drivers", "problems", "metrics"):
        shutil.copytree(REPO / "bench" / sub, dst / "bench" / sub)
    shutil.copy(REPO / "bench" / "peaks.json", dst / "bench" / "peaks.json")
    return dst


@pytest.fixture
def tiny(tmp_path) -> Path:
    root = copy_bench(tmp_path)
    for name, shrink in TINY.items():
        path = root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        shrink(cfg)
        path.write_text(json.dumps(cfg))
    return root


def run_tiny(root: Path, workload: str, seed: int = 2**31 + 5, **kw) -> dict:
    from bench import harness
    return harness.run_cell(workload, seed, 0.01, False, root=root,
                            bench=root / "bench", require_tpu=False, **kw)
