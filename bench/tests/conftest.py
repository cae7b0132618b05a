"""Shared set-up of the benchmark's own tests (CPU only).

Eight virtual CPU devices are forced before JAX starts, so that a cell
that asks for four chips is rehearsed here as well.  ``tiny`` copies the
benchmark's files (``BENCHMARK.json`` and the configurations, mixes,
drivers, problems, metrics and peaks under ``bench/``) into a temporary
checkout and shrinks every configuration by the ``tiny`` object in its
own file, so that a whole run of a cell takes seconds on the CPU.
Nothing here touches a TPU.
"""
import json
import os
import shutil
from pathlib import Path

_FLAG = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _FLAG).strip()

import pytest  # noqa: E402

REPO = Path(__file__).resolve().parents[2]


def copy_bench(dst: Path) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for sub in ("configs", "traffic", "drivers", "problems", "metrics"):
        shutil.copytree(REPO / "bench" / sub, dst / "bench" / sub)
    shutil.copy(REPO / "bench" / "peaks.json", dst / "bench" / "peaks.json")
    return dst


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s entries, nested objects merged key by key."""
    out = dict(base)
    for key, value in over.items():
        out[key] = (merge(out[key], value) if isinstance(value, dict)
                    and isinstance(out.get(key), dict) else value)
    return out


def shrink(root: Path) -> None:
    """Give every configuration under ``root`` its own ``tiny`` sizes."""
    for path in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        path.write_text(json.dumps(merge(cfg, cfg["tiny"])))


@pytest.fixture
def tiny(tmp_path) -> Path:
    root = copy_bench(tmp_path)
    shrink(root)
    return root


def run_tiny(root: Path, workload: str, seed: int = 2**31 + 5, **kw) -> dict:
    from bench import harness
    return harness.run_cell(workload, seed, 0.01, False, root=root,
                            bench=root / "bench", require_tpu=False, **kw)
