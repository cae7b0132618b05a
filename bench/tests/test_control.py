"""The control: the plain reference put in the program's place at the
precision below the configuration's.  The adaptive control (float32 at
Precision.HIGH) fails its limits at the cell's size, which a test run
cannot hold, so here it has to read at least three times what the
program reads on a mesh a test run can hold.  The partition's control
(the per-axis box map) fails its limit at any size."""
import json

from bench.tests.conftest import run_tiny


def test_adapt_control_reads_above_the_program(tiny):
    path = tiny / "bench" / "configs" / "helmholtz-cyl.json"
    cfg = json.loads(path.read_text())
    cfg["mesh"].update(n_axial=20, n_cross=4)
    path.write_text(json.dumps(cfg))
    out = run_tiny(tiny, "helmholtz-cyl.adapt", control=True)
    assert out["correct"]
    ctl, prog = out["control_checks"], out["checks"]
    assert ctl["u_gap"] >= 3 * prog["u_gap"]["value"], (ctl, prog)


def test_partition_control_fails_its_limit(tiny):
    out = run_tiny(tiny, "helmholtz-cyl-p4.adapt", control=True)
    assert out["correct"]
    gap = out["checks"]["part_gap"]
    assert out["control_checks"]["part_gap"] > gap["limit"] >= gap["value"]
