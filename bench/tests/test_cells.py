"""Each traffic mix rehearsed at a tiny size on the CPU, through set-up,
window and check; and the entry point's refusal without a TPU."""
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.conftest import REPO, run_tiny

CELLS = [w["name"] for w in harness.load_manifest()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_is_correct(tiny, cell):
    out = run_tiny(tiny, cell)
    manifest = harness.load_manifest(tiny)
    config = harness.load_config(
        manifest, harness.find(manifest["workloads"], cell, "cell")["config"],
        tiny)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["checks"]) == set(config["limits"])
    assert list(out)[-1] == "checks"
    want = {m["name"] for m in harness.cell_metrics(manifest, cell,
                                                    "end_to_end")}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == harness.find(
        manifest["workloads"], cell, "cell")["chips"]


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_traced_run_reads_host_metrics(tiny, cell):
    """Off the TPU the trace has no device plane: the metrics read from it
    are left out, the ones read from the program's spans and counters
    are there."""
    from bench import harness as h
    out = h.run_cell(cell, 11, 0.01, True, root=tiny, bench=tiny / "bench",
                     require_tpu=False)
    manifest = h.load_manifest(tiny)
    for m in h.cell_metrics(manifest, cell, "per_layer"):
        assert (m["name"] in out["metrics"]) == (m["source"]
                                                 != "device_trace"), m
    assert "breakdown" not in out and out["correct"]


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "bench" / "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=REPO, timeout=120)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())
    assert "refusing to run" in p.stderr


def test_seed_scales_the_solution_exactly(tiny):
    """Seeds of the adaptive cell differ in the amplitude 2**k alone: the
    same meshes and CG counts, every value scaled exactly."""
    import jax
    import numpy as np
    manifest = harness.load_manifest(tiny)
    cfg = harness.load_config(manifest, "helmholtz-cyl", tiny)
    mix = harness.load_mix("adapt", tiny / "bench")
    driver = harness.load_driver(mix["driver"], tiny / "bench")
    runs = {}
    for seed in range(40):
        k = int(harness.rng_for(seed).integers(
            mix["amplitude_exponents"][0], mix["amplitude_exponents"][1] + 1))
        runs.setdefault(k, seed)
        if len(runs) == 2:
            break
    (ka, sa), (kb, sb) = runs.items()
    drv = [driver(cfg, mix, s, jax.devices()[:1], tiny / "bench")
           for s in (sa, sb)]
    for d in drv:
        d.window(0.01, False)
    a, b = (d.cycles[0] for d in drv)
    assert [s["n_tets"] for s in a["steps"]] == \
        [s["n_tets"] for s in b["steps"]]
    assert [s["cg_iters"] for s in a["steps"]] == \
        [s["cg_iters"] for s in b["steps"]]
    np.testing.assert_array_equal(np.asarray(a["u"]) * 2.0 ** (kb - ka),
                                  np.asarray(b["u"]))
