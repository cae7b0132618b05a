"""Configurations, mixes, drivers, problems and metrics are found by name:
files added next to the existing ones are picked up without an edit to
any of them."""
import json
import shutil

import pytest

from bench import harness
from bench.tests.conftest import REPO, shrink

DRIVER = '''
import time

import jax
import jax.numpy as jnp
import numpy as np


class Driver:
    def __init__(self, config, mix, seed, devices, bench):
        self.fn = jax.jit(lambda x: jnp.sum(x * x))
        rng = np.random.default_rng(seed)
        self.x = rng.random(config["n"]).astype(np.float32)
        self.fn(self.x).block_until_ready()

    def window(self, seconds, traced):
        t0 = time.perf_counter()
        self.got = [float(self.fn(self.x)) for _ in range(3)]
        t1 = time.perf_counter()
        return {"t_start": t0, "t_end": t1, "attempted": 3,
                "e2e": {"sum_ms": 1e3 * (t1 - t0) / 3}, "calls": 3}

    def check(self, lowp=False):
        want = float((self.x.astype(np.float64) ** 2).sum())
        return {"sum_gap": max(abs(g - want) / want for g in self.got)}
'''


def add(tiny, *, configs=(), workloads=(), end_to_end=(), per_layer=()):
    manifest = json.loads((tiny / "BENCHMARK.json").read_text())
    manifest["configs"] += configs
    manifest["workloads"] += workloads
    for m in manifest["end_to_end"]:
        if m["name"] == "adapt_step_s":
            m["workloads"] += [w["name"] for w in workloads
                               if w["traffic"] != "sums"]
    manifest["end_to_end"] += end_to_end
    manifest["per_layer"] += per_layer
    (tiny / "BENCHMARK.json").write_text(json.dumps(manifest))


def test_added_config_problem_mix_and_metric_are_found(tiny):
    bench = tiny / "bench"
    shutil.copy(bench / "problems" / "helmholtz.py",
                bench / "problems" / "helmholtz-copy.py")
    cfg = json.loads((bench / "configs" / "helmholtz-cyl.json").read_text())
    cfg["name"] = "helmholtz-short"
    cfg["problem"]["module"] = "helmholtz-copy"
    cfg["mesh"]["n_axial"] = 4
    (bench / "configs" / "helmholtz-short.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "adapt.json").read_text())
    mix["steps_per_cycle"] = 1
    (bench / "traffic" / "one-step.json").write_text(json.dumps(mix))
    (bench / "metrics" / "tets.extra.py").write_text(
        "def read(rec):\n    return float(rec['steps'][0]['n_tets'])\n")
    add(tiny,
        configs=[{"name": "helmholtz-short",
                  "source": "https://arxiv.org/abs/1702.00312",
                  "file": "bench/configs/helmholtz-short.json",
                  "reduced": [], "why": "an added configuration"}],
        workloads=[{"name": "helmholtz-short.one-step",
                    "config": "helmholtz-short", "traffic": "one-step",
                    "chips": 1, "why": "an added cell"}],
        per_layer=[{"name": "tets.extra", "unit": "tets",
                    "better": "higher", "source": "program_counter",
                    "layer": "mesh refinement (host)",
                    "moves": "adapt_step_s",
                    "workloads": ["helmholtz-short.one-step"]}])
    out = harness.run_cell("helmholtz-short.one-step", 9, 0.01, True,
                           root=tiny, bench=bench, require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["tets.extra"]["value"] == 4 * 2 * 2 * 6
    assert "cg_iters.adapt" not in out["metrics"]
    plain = harness.run_cell("helmholtz-short.one-step", 9, 0.01, False,
                             root=tiny, bench=bench, require_tpu=False)
    assert set(plain["metrics"]) == {"adapt_step_s", "setup_s"}
    assert plain["correct"] and plain["attempted"] >= 1


def test_added_config_and_four_chip_cell_are_found(tiny):
    """What the next configuration brings: its file at full size with
    ``tiny`` sizes of its own, and a cell on four chips appended to the
    lists of the metrics it reports; no file that was there changes."""
    bench = tiny / "bench"
    cfg = json.loads((REPO / "bench" / "configs"
                      / "helmholtz-cyl-p4.json").read_text())
    cfg["name"] = "helmholtz-thick"
    cfg["mesh"]["radius"] = 0.75
    cfg["tiny"] = {"mesh": {"n_axial": 6, "n_cross": 2}}
    path = bench / "configs" / "helmholtz-thick.json"
    path.write_text(json.dumps(cfg))
    shrink(tiny)
    assert json.loads(path.read_text())["mesh"]["n_axial"] == 6
    cell = "helmholtz-thick.adapt"
    add(tiny,
        configs=[{"name": "helmholtz-thick",
                  "source": "https://arxiv.org/abs/1702.00312",
                  "file": "bench/configs/helmholtz-thick.json",
                  "reduced": [], "why": "an added configuration"}],
        workloads=[{"name": cell, "config": "helmholtz-thick",
                    "traffic": "adapt", "chips": 4,
                    "why": "an added four-chip cell"}])
    manifest = json.loads((tiny / "BENCHMARK.json").read_text())
    for m in manifest["per_layer"]:
        if "helmholtz-cyl-p4.adapt" in m.get("workloads", []):
            m["workloads"].append(cell)
    (tiny / "BENCHMARK.json").write_text(json.dumps(manifest))
    plain = harness.run_cell(cell, 2**40 + 3, 0.01, False, root=tiny,
                             bench=bench, require_tpu=False)
    assert plain["correct"], plain["checks"]
    assert plain["device"]["count"] == 4
    assert set(plain["metrics"]) == {"adapt_step_s", "setup_s"}
    assert {"part_gap", "imbalance_gap"} <= set(plain["checks"])
    traced = harness.run_cell(cell, 2**40 + 4, 0.01, True, root=tiny,
                              bench=bench, require_tpu=False)
    assert traced["correct"], traced["checks"]
    assert {"halo_bytes.adapt", "migrated_frac.adapt", "cg_iters.adapt",
            "balance_stage_s.adapt"} <= set(traced["metrics"])
    assert traced["metrics"]["halo_bytes.adapt"]["value"] > 0


def test_added_driver_is_found(tiny):
    bench = tiny / "bench"
    (bench / "drivers" / "sums.py").write_text(DRIVER)
    (bench / "traffic" / "sums.json").write_text(json.dumps(
        {"driver": "sums", "what": "three jitted sums of squares"}))
    (bench / "configs" / "vector.json").write_text(json.dumps(
        {"name": "vector", "n": 1000, "reduced": [],
         "limits": {"sum_gap": 1e-5}}))
    add(tiny,
        configs=[{"name": "vector", "source": "https://example.org/vector",
                  "file": "bench/configs/vector.json", "reduced": [],
                  "why": "an added configuration"}],
        workloads=[{"name": "vector.sums", "config": "vector",
                    "traffic": "sums", "chips": 1, "why": "an added cell"}],
        end_to_end=[{"name": "sum_ms", "unit": "ms", "better": "lower",
                     "bound": 0.05, "source": "host_clock",
                     "workloads": ["vector.sums"]}],
        per_layer=[{"name": "calls.sums", "unit": "calls",
                    "better": "higher", "source": "program_counter",
                    "layer": "sums", "moves": "sum_ms",
                    "workloads": ["vector.sums"]}])
    (bench / "metrics" / "calls.sums.py").write_text(
        "def read(rec):\n    return rec.get('calls')\n")
    out = harness.run_cell("vector.sums", 5, 0.01, False, root=tiny,
                           bench=bench, require_tpu=False)
    assert out["correct"] and out["attempted"] == 3
    assert set(out["metrics"]) == {"sum_ms", "setup_s"}
    traced = harness.run_cell("vector.sums", 5, 0.01, True, root=tiny,
                              bench=bench, require_tpu=False)
    assert traced["metrics"] == {"calls.sums": {"value": 3,
                                                "unit": "calls"}}


@pytest.mark.parametrize("kind", ["drivers", "problems", "metrics"])
def test_a_missing_file_is_named(tiny, kind):
    with pytest.raises(KeyError, match=f"bench/{kind}/absent.py"):
        harness.load_module(kind, "absent", tiny / "bench")
