"""The program-span probe's reductions (``bench/spans.py``): device idle
by program span, device time by named scope, the per-step numbers, on a
hand-made trace; and the recorded trace read through its plain form."""
import json
from pathlib import Path

import pytest

from bench import spans, trace

FIXTURE = Path(__file__).parent / "fixtures" / "trace_dlb_one_rebalance.json"


def hand_made():
    # window 0..100 ns; on the device jit_solve runs 10..40 (its loop ops
    # under fem_matvec 10..30, a vector update 30..40) and jit_pack 60..70;
    # the host is in adapt/solve 0..50 with its children adapt/solve/pcg
    # 0..8 and adapt/solve/wait 8..45, and in adapt/pack 50..90 with
    # adapt/pack/migrate 55..65; "other" is not a program span
    path = "jit(solve)/while/body/shard_map/fem_matvec/add"
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_solve(7)", 10, 30],
                                               ["jit_pack(8)", 60, 10]]},
            {"name": "XLA Ops", "events": [
                ["%fusion.35 = f32[8] fusion()", 10, 12, path],
                ["%fusion.36 = f32[8] fusion()", 22, 8,
                 path.replace("add", "mul")],
                ["%fusion.2 = f32[8] fusion()", 30, 10,
                 "jit(solve)/while/body/add"],
                ["%all-to-all.1 = f32[8] all-to-all()", 60, 10]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["bench/window", 0, 100], ["bench/solve", 0, 50],
                ["adapt/solve", 0, 50], ["adapt/solve/pcg", 0, 8],
                ["adapt/solve/wait", 8, 37], ["adapt/pack", 50, 40],
                ["adapt/pack/migrate", 55, 10], ["other", 90, 5]]}]}]}


NAMES = {"adapt/solve", "adapt/solve/pcg", "adapt/solve/wait",
         "adapt/pack", "adapt/pack/migrate"}


def test_program_gaps_give_idle_to_the_innermost_program_span():
    gaps = dict(spans.program_gaps(hand_made(), NAMES))
    # idle: 0..10 (pcg 0..8, wait 8..10), 40..50 (wait 40..45, solve's
    # self 45..50), 50..60 (pack 50..55, migrate 55..60), 70..100 (pack
    # 70..90, nothing 90..100)
    assert gaps == pytest.approx({
        "adapt/solve/pcg": 8e-9, "adapt/solve/wait": 7e-9,
        "adapt/solve": 5e-9, "adapt/pack": 25e-9,
        "adapt/pack/migrate": 5e-9, "unattributed": 10e-9})
    # the harness's own reduction of the same trace is untouched
    r = trace.reduce_trace(spans.plain(hand_made()))
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert spans.program_gaps({"planes": hand_made()["planes"][1:]},
                              NAMES) is None


def test_scope_seconds_per_program():
    got = spans.scope_s(hand_made())
    assert got == {"jit_solve": {"fem_matvec": pytest.approx(20e-9)}}
    # a part of the path, not a substring: "fem_matvec_x" is another scope
    t = hand_made()
    ops = t["planes"][0]["lines"][1]["events"]
    ops[0][3] = ops[0][3].replace("fem_matvec", "fem_matvec_x")
    assert spans.scope_s(t)["jit_solve"]["fem_matvec"] == \
        pytest.approx(8e-9)
    # events without a scope path (a CPU trace, the recorded fixture) give
    # no scope time
    assert spans.scope_s(spans.plain(hand_made())) == {}


def test_per_step_numbers():
    steps = [{"cg_iters": 3}, {"cg_iters": 2}]
    span_events = [
        {"name": "adapt/pack", "dur_us": 4.0e6},
        {"name": "adapt/elements", "dur_us": 0.5e6},
        {"name": "adapt/solve/assemble", "dur_us": 1.5e6},
        {"name": "adapt/solve/pcg", "dur_us": 3.0e6},
        {"name": "adapt/solve/pcg", "dur_us": 1.0e6}]
    jit_events = [{"kind": "jit_traces"}] * 5 + [{"kind": "jit_lowerings"}]
    got = spans.per_step(steps, span_events, jit_events,
                         {"jit_solve": {"fem_matvec": 0.05}})
    assert got == pytest.approx({"pack_s": 2.0, "assemble_s": 1.0,
                                 "pcg_dispatch_s": 2.0, "retraces": 2.5,
                                 "matvec_ms": 10.0})
    # a program without the spans or the scope reads only what it has
    assert spans.per_step(steps, [], [], {}) == {"retraces": 0.0}
    assert spans.per_step([], span_events, jit_events, {}) == {}


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_trace_reads_alike_with_scope_paths():
    """Events that carry a scope path reduce, through ``plain``, to every
    value the recorded trace gives without one."""
    t = json.loads(FIXTURE.read_text())
    want = trace.reduce_trace(t)
    scoped = json.loads(FIXTURE.read_text())
    for plane in scoped["planes"]:
        if trace.DEVICE_PLANE.match(plane["name"]):
            for ln in plane["lines"]:
                for ev in ln["events"]:
                    ev.append("jit(fn)/fem_matvec/op")
    assert trace.reduce_trace(spans.plain(scoped)) == want
    assert spans.scope_s(scoped)["jit_fn"]["fem_matvec"] > 0


# -- the XSpace protobuf, written by hand for the reader ----------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _msg(field, *parts):
    body = b"".join(p.encode() if isinstance(p, str) else p for p in parts)
    return _varint(field << 3 | 2) + _varint(len(body)) + body


def _double(field, x):
    import struct
    return _varint(field << 3 | 1) + struct.pack("<d", x)


def _xspace():
    stat_meta = (_msg(5, _int(1, 7), _msg(2, _msg(2, "tf_op")))
                 + _msg(5, _int(1, 8), _msg(2, _msg(2, "flops")))
                 + _msg(5, _int(1, 9), _msg(2, _msg(2, "jit(s)/ref/op:"))))
    event_meta = (
        # op 1: its path as a string; op 2: as a reference to a stat's
        # name; op 3: no path at all
        _msg(4, _int(1, 1), _msg(2, _int(1, 1), _msg(2, "%fusion.1 = f"),
                                 _msg(5, _int(1, 8), _double(2, 3.0)),
                                 _msg(5, _int(1, 7),
                                      _msg(5, "jit(s)/fem_matvec/add:"))))
        + _msg(4, _int(1, 2), _msg(2, _int(1, 2), _msg(2, "%copy.2 = f"),
                                   _msg(5, _int(1, 7), _int(7, 9))))
        + _msg(4, _int(1, 3), _msg(2, _int(1, 3), _msg(2, "%while.3 = f"))))
    ops = _msg(3, _msg(2, "XLA Ops"),
               *[_msg(4, _int(1, m), _int(2, 10 * m), _int(3, 5))
                 for m in (1, 3, 2, 1)])
    device = _msg(1, _int(1, 1), _msg(2, "/device:TPU:0"), stat_meta,
                  event_meta, ops)
    host = _msg(1, _int(1, 2), _msg(2, "/host:CPU"),
                _msg(3, _msg(2, "python"),
                     _msg(4, _int(1, 1), _int(3, 4))))
    return host + device


def test_metadata_stat_reads_the_op_path_of_each_device_event():
    got = spans.metadata_stat(_xspace(), "tf_op")
    assert got == {("/device:TPU:0", "XLA Ops"): [
        ("%fusion.1 = f", "jit(s)/fem_matvec/add:"),
        ("%while.3 = f", None),
        ("%copy.2 = f", "jit(s)/ref/op:"),
        ("%fusion.1 = f", "jit(s)/fem_matvec/add:")]}
    assert spans.metadata_stat(_xspace(), "no_such_stat")[
        ("/device:TPU:0", "XLA Ops")][0] == ("%fusion.1 = f", None)


def test_events_from_a_cpu_profile_read_as_the_harness_reads_them(tmp_path):
    """Off the TPU there is no device plane: the probe's reading of a
    profile is the harness's own, with no scope paths."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 3)
    x = jnp.arange(4.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    got = spans.events_from_xplane(str(tmp_path))
    assert got == trace.events_from_xplane(str(tmp_path))
    assert any(ev[0] == trace.WINDOW_SPAN for pl in got["planes"]
               for ln in pl["lines"] for ev in ln["events"])


def test_events_from_xplane_attach_the_path_to_device_ops(tmp_path):
    (tmp_path / "x.xplane.pb").write_bytes(_xspace())
    got = spans.events_from_xplane(str(tmp_path))
    dev = [pl for pl in got["planes"] if pl["name"] == "/device:TPU:0"][0]
    ops = dev["lines"][0]["events"]
    assert [ev[3:] for ev in ops] == [["jit(s)/fem_matvec/add:"], [],
                                      ["jit(s)/ref/op:"],
                                      ["jit(s)/fem_matvec/add:"]]
    assert [ev[:3] for ev in ops] == trace.events_from_xplane(
        str(tmp_path))["planes"][1]["lines"][0]["events"]
