#!/usr/bin/env python3
"""Where the adaptive step's host time goes, read from the program's own
spans (``repro.telemetry``) on the profiler's clock.

    python3 bench/spans.py --workload <cell> --seed <n> [--out FILE]

Sets a cell up as ``bench/run.py`` does, then runs one traced window
(profiler on, Python tracer off) with a ``repro.telemetry.Tracer``
installed.  The program's spans then sit on the profiler's host plane
beside the harness's ``bench/<stage>`` spans, on the device's clock, and
the tracer records every program JAX traced, lowered, compiled or loaded
from its cache in the window.  Prints one JSON line, also written to
``--out``:

- ``program_gaps``: device-idle seconds by the innermost program span
  open over them (``unattributed`` where none was), in the form
  ``bench/trace.py`` gives ``idle_gaps`` by harness span;
- ``scope_s``: device seconds of the operations under each named scope
  of ``SCOPES``, per program;
- ``per_step``: per adaptive step of the window, the host seconds in
  ``adapt/pack``, in ``adapt/elements`` plus ``adapt/solve/assemble``
  and in ``adapt/solve/pcg``, the top-level programs traced, and the
  device milliseconds per CG iteration of the operations under
  ``fem_matvec`` in ``jit_solve``;
- ``span_s``: host seconds per span name per step, and ``jit``: the
  tracer's ``jit_summary()``.

``bench/run.py`` reads none of this: it is a probe from which the
benchmark's per-layer metrics of these layers can be built.  The profile
is left under ``.bench_trace/spans-<cell>/`` for reading by hand.

The scope path of a device operation is the ``SCOPE_STAT`` (``tf_op``)
stat of its event's metadata (``XEventMetadata.stats``, not the event's
own stats) on the ``XLA Ops`` line of a TPU plane: the operation's
``op_name``, such as ``jit(solve)/while/body/jit(local_apply_owned)/
fem_matvec/jit(fem_matvec_jnp)/gather:`` on a TPU v5e.
``jax.profiler.ProfileData`` does not expose metadata stats, so
``metadata_stat`` reads them from the ``.xplane.pb`` itself.  The CPU
backend's trace has no device plane, so off the TPU ``scope_s`` is empty.

JAX's persistent compile cache leaves op metadata out of its key
(``jax_compilation_cache_include_metadata_in_key`` is off by default), so
an executable loaded from an entry compiled before a scope existed
carries the older paths: ``fem_matvec`` shows only in programs compiled
from source that has it.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import json
import os
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace  # noqa: E402

SCOPE_STAT = "tf_op"
# the named scope the program puts on the matvec (fem.parallel.MATVEC_SCOPE)
MATVEC_SCOPE = "fem_matvec"
SCOPES = (MATVEC_SCOPE,)
SOLVE_PROGRAM = "jit_solve"


def events_from_xplane(trace_dir: str, stat: str = SCOPE_STAT) -> dict:
    """``bench.trace.events_from_xplane``'s form, with the value of the
    ``stat`` stat of a device plane's event, where it has one, as a
    fourth element of the event."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    path = max(files, key=os.path.getmtime)
    tr = trace.events_from_xplane(os.path.dirname(path))
    with open(path, "rb") as f:
        paths = metadata_stat(f.read(), stat)
    for plane in tr["planes"]:
        for ln in plane["lines"]:
            got = paths.get((plane["name"], ln["name"]))
            if not got or len(got) != len(ln["events"]):
                continue
            for ev, (name, value) in zip(ln["events"], got):
                if value is not None and name == ev[0]:
                    ev.append(value)
    return tr


# -- the XSpace protobuf, read by hand ---------------------------------------
# jax.profiler.ProfileData gives an event's own stats but not those of its
# metadata, where a TPU keeps an operation's op_name; the few fields read
# here are those of tsl/profiler/protobuf/xplane.proto:
#   XSpace.planes 1; XPlane.name 2, lines 3, event_metadata 4 (map entry:
#   key 1, value 2), stat_metadata 5 (map entry); XLine.name 2, events 4;
#   XEvent.metadata_id 1; XEventMetadata.name 2, stats 5; XStat.metadata_id
#   1, str_value 5, ref_value 7; XStatMetadata.name 2.

def _varint(b: bytes, i: int):
    shift = out = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes):
    """``(field number, value)`` of a message: an int for a varint, the
    bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _map_entry(b: bytes):
    f = dict(_fields(b))
    return f.get(1, 0), f.get(2, b"")


def metadata_stat(xspace: bytes, stat: str) -> dict:
    """``{(plane, line): [(event name, value or None), ...]}``, in file
    order, for the events of the device planes: the string value of the
    ``stat`` stat of each event's metadata."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        name, lines, emeta, smeta = "", [], {}, {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = v.decode()
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                emeta.__setitem__(*_map_entry(v))
            elif pf == 5:
                k, m = _map_entry(v)
                smeta[k] = dict(_fields(m)).get(2, b"").decode()
        if not trace.DEVICE_PLANE.match(name):
            continue
        want = {k for k, v in smeta.items() if v == stat}
        meta = {}
        for k, m in emeta.items():
            value, ev_name = None, ""
            for mf, mv in _fields(m):
                if mf == 2:
                    ev_name = mv.decode(errors="replace")
                elif mf == 5:
                    st = dict(_fields(mv))
                    if st.get(1) in want:
                        value = (st[5].decode(errors="replace") if 5 in st
                                 else smeta.get(st.get(7)))
            meta[k] = (ev_name, value)
        for line in lines:
            ln_name, events = "", []
            for lf, lv in _fields(line):
                if lf == 2:
                    ln_name = lv.decode()
                elif lf == 4:
                    mid = next((v for ef, v in _fields(lv) if ef == 1), 0)
                    events.append(meta.get(mid, ("", None)))
            out[(name, ln_name)] = events
    return out


def plain(tr: dict) -> dict:
    """The trace in ``bench.trace``'s own form: each event's fourth
    element, the scope path, dropped."""
    return {"planes": [
        {"name": pl["name"],
         "lines": [{"name": ln["name"],
                    "events": [ev[:3] for ev in ln["events"]]}
                   for ln in pl["lines"]]}
        for pl in tr["planes"]]}


def _window_spans_devices(tr: dict, names):
    """The window, the host events named in ``names`` as
    ``(start, end, name)``, and each device plane's lines by name."""
    window, spans, devices = None, [], []
    for plane in tr["planes"]:
        if trace.DEVICE_PLANE.match(plane["name"]):
            devices.append({ln["name"]: ln["events"]
                            for ln in plane["lines"]})
            continue
        for ln in plane["lines"]:
            for ev in ln["events"]:
                name, s, d = ev[:3]
                if name == trace.WINDOW_SPAN:
                    window = (s, s + d)
                elif name in names:
                    spans.append((s, s + d, name))
    return window, spans, devices


def program_gaps(tr: dict, names) -> list | None:
    """Device-idle seconds in the ``bench/window`` span by the innermost
    host span named in ``names`` that is open over them (``unattributed``
    where none is), as ``[[name, seconds], ...]``, longest first.  Idle
    is judged on the first device, as ``bench/trace.py`` judges it."""
    window, spans, devices = _window_spans_devices(tr, set(names))
    if window is None or not devices:
        return None
    lo, hi = window
    busy = trace._union(trace._clip(
        [(ev[1], ev[1] + ev[2]) for ev in devices[0].get(trace.OPS_LINE, [])],
        lo, hi))
    gaps = defaultdict(int)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    cuts = sorted({x for sp in spans for x in sp[:2]})
    for s, e in zip(edges[0::2], edges[1::2]):
        bounds = [s] + [x for x in cuts if s < x < e] + [e]
        for a, b in zip(bounds, bounds[1:]):
            if b <= a:
                continue
            mid = (a + b) / 2
            inside = [sp for sp in spans if sp[0] <= mid < sp[1]]
            label = (min(inside, key=lambda sp: sp[1] - sp[0])[2] if inside
                     else "unattributed")
            gaps[label] += b - a
    return [[k, v / 1e9] for k, v in sorted(gaps.items(),
                                            key=lambda kv: -kv[1])]


def scope_s(tr: dict, scopes=SCOPES) -> dict:
    """``{program: {scope: seconds}}``: device seconds, averaged over the
    chips, of the operations in the window whose scope path (an event's
    fourth element) holds a scope of ``scopes`` as one of its parts."""
    window, _, devices = _window_spans_devices(tr, ())
    out = defaultdict(lambda: defaultdict(float))
    if window is None or not devices:
        return {}
    lo, hi = window
    for dev in devices:
        mods = sorted((s, s + d, trace.module_name(name))
                      for name, s, d, *_ in dev.get(trace.MODULES_LINE, []))
        starts = [m[0] for m in mods]
        for ev in dev.get(trace.OPS_LINE, []):
            if len(ev) < 4 or not (ev[1] + ev[2] > lo and ev[1] < hi):
                continue
            parts = ev[3].split("/")
            k = bisect.bisect_right(starts, ev[1]) - 1
            owner = mods[k][2] if k >= 0 and ev[1] < mods[k][1] else "?"
            for scope in scopes:
                if scope in parts:
                    out[owner][scope] += ev[2] / len(devices) / 1e9
    return {p: dict(s) for p, s in out.items()}


def span_seconds(spans) -> dict:
    """Host seconds per span name, from ``SpanEvent``-like dicts."""
    out = defaultdict(float)
    for sp in spans:
        out[sp["name"]] += sp["dur_us"] / 1e6
    return dict(out)


def per_step(steps: list, spans: list, jit_events: list,
             scopes: dict) -> dict:
    """The window's per-step numbers from its steps (``cg_iters``), the
    tracer's spans and jit events (as dicts) and ``scope_s``.  A number
    whose source is missing is left out."""
    n = len(steps)
    if not n:
        return {}
    sec = span_seconds(spans)
    out = {}
    if "adapt/pack" in sec:
        out["pack_s"] = sec["adapt/pack"] / n
    if "adapt/solve/assemble" in sec:
        out["assemble_s"] = (sec["adapt/solve/assemble"]
                             + sec.get("adapt/elements", 0.0)) / n
    if "adapt/solve/pcg" in sec:
        out["pcg_dispatch_s"] = sec["adapt/solve/pcg"] / n
    out["retraces"] = sum(e["kind"] == "jit_traces" for e in jit_events) / n
    mv = scopes.get(SOLVE_PROGRAM, {}).get(MATVEC_SCOPE)
    iters = sum(s["cg_iters"] for s in steps)
    if mv is not None and iters:
        out["matvec_ms"] = 1e3 * mv / iters
    return out


def run(workload: str, seed: int, seconds: float, *, root: Path = ROOT,
        bench: Path = harness.BENCH, require_tpu: bool = True) -> dict:
    """Set the cell up, run one traced window under a ``Tracer`` and
    reduce it (see the module's docstring)."""
    import jax
    from repro import telemetry
    manifest = harness.load_manifest(root)
    cell = harness.find(manifest["workloads"], workload, "workload")
    config = harness.load_config(manifest, cell["config"], root)
    mix = harness.load_mix(cell["traffic"], bench)
    devices = harness.chips(cell["chips"], require_tpu)
    t0 = time.perf_counter()
    drv = harness.load_driver(mix["driver"], bench)(config, mix, seed,
                                                    devices, bench)
    setup_s = time.perf_counter() - t0
    trace_dir = root / ".bench_trace" / f"spans-{workload}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    tracer = telemetry.Tracer()
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN), \
                telemetry.tracing(tracer):
            rec = drv.window(seconds, True)
    finally:
        jax.profiler.stop_trace()
    tr = events_from_xplane(str(trace_dir))
    spans = [dataclasses.asdict(e) for e in tracer.events]
    jit_events = [dataclasses.asdict(e) for e in tracer.jit_events]
    steps = rec.get("steps", [])
    scopes = scope_s(tr)
    return {
        "workload": workload, "seed": seed, "setup_s": setup_s,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "window_s": rec["t_end"] - rec["t_start"], "steps": steps,
        "per_step": per_step(steps, spans, jit_events, scopes),
        "program_gaps": program_gaps(tr, {sp["name"] for sp in spans}),
        "idle_gaps": (trace.reduce_trace(plain(tr)) or {}).get("idle_gaps"),
        "scope_s": scopes,
        "span_s": {k: v / max(len(steps), 1)
                   for k, v in span_seconds(spans).items()},
        "jit": tracer.jit_summary(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    harness.use_compile_cache()
    try:
        out = run(args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 1
    line = json.dumps(out)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
