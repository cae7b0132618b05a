"""The reduction from a profiler trace to busy time, program time and idle
gaps, on a hand-made trace and on one recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "fixtures" / "trace_dlb_one_rebalance.json"


def hand_made():
    # window 0..100; device ops at 10..30 and 20..40 (overlap), 60..70,
    # and 95..120 (cut at the window's end); programs A (10..40) and
    # B (60..70); the host was in "solve" 0..50 and "balance" 50..100
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_a(1)", 10, 30],
                                               ["jit_b(2)", 60, 10],
                                               ["jit_a(1)", 95, 25]]},
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = f32[4] fusion()", 10, 20],
                ["%while.2 = (f32[4]) while()", 20, 20],
                ["%all-to-all.3 = f32[4] all-to-all()", 60, 10],
                ["%fusion.1 = f32[4] fusion()", 95, 25]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["bench/window", 0, 100],
                                          ["bench/solve", 0, 50],
                                          ["bench/balance", 50, 50],
                                          ["other", 0, 5]]}]}]}


def test_hand_made_trace():
    r = trace.reduce_trace(hand_made())
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: 10..40, 60..70, 95..100
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["module_s"]["jit_a"] == pytest.approx(55e-9)
    assert r["module_s"]["jit_b"] == pytest.approx(10e-9)
    assert r["collective_s"] == pytest.approx(10e-9)
    gaps = dict(r["idle_gaps"])
    # idle 0..10 and 40..50 under solve, 50..60 and 70..95 under balance
    assert gaps == pytest.approx({"solve": 20e-9, "balance": 35e-9})
    ops = dict(r["device_ops"])
    assert ops["jit_a/fusion.1"] == pytest.approx(45e-9)
    assert ops["jit_b/all-to-all.3"] == pytest.approx(10e-9)


def test_no_device_or_no_window_reads_nothing():
    t = hand_made()
    assert trace.reduce_trace({"planes": t["planes"][1:]}) is None
    t["planes"][1]["lines"][0]["events"] = [["bench/solve", 0, 50]]
    assert trace.reduce_trace(t) is None


def test_names():
    assert trace.module_name("jit_solve(16546)") == "jit_solve"
    assert trace.op_name("%fusion.35 = f32[8] fusion(x)") == "fusion.35"


def busy_by_sweep(events, lo, hi):
    """Busy time by a sweep over every start and end, independent of the
    sort-and-merge in bench/trace.py."""
    points = sorted([(max(s, lo), 1) for _, s, d in events
                     if s + d > lo and s < hi]
                    + [(min(s + d, hi), -1) for _, s, d in events
                       if s + d > lo and s < hi])
    busy, depth, last = 0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_trace():
    t = json.loads(FIXTURE.read_text())
    r = trace.reduce_trace(t)
    dev = [p for p in t["planes"] if p["name"] == "/device:TPU:0"][0]
    ops = [ln for ln in dev["lines"] if ln["name"] == "XLA Ops"][0]["events"]
    host = [e for p in t["planes"] if p["name"] != "/device:TPU:0"
            for ln in p["lines"] for e in ln["events"]]
    lo, d = [(s, d) for n, s, d in host if n == "bench/window"][0]
    assert r["window_s"] == pytest.approx(d / 1e9)
    assert r["busy_s"] == pytest.approx(busy_by_sweep(ops, lo, lo + d) / 1e9)
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-12
    # one rebalance runs one balance program
    assert max(r["module_s"], key=r["module_s"].get) == "jit_fn"
