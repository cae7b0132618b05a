"""repro.telemetry: tracer/span semantics, registry typing, exporters,
async-dispatch timing regression, and the null tracer's zero-cost claim."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import Balancer, BalanceSpec
from repro.fem import AdaptSpec, AdaptiveSession, cylinder_mesh
from repro.telemetry import export as texport


def _coords(n, seed=0):
    return jnp.asarray(np.random.RandomState(seed).rand(n, 3), jnp.float32)


# ---------------------------------------------------------------------------
# Tracer / span semantics
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_monotonic_ts():
    tr = telemetry.Tracer()
    with tr.span("outer", kind="test"):
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    by_name = {e.name: e for e in tr.events}
    assert by_name["outer"].depth == 0
    assert by_name["inner"].depth == 1
    assert by_name["inner2"].depth == 1
    assert by_name["outer"].attrs == {"kind": "test"}
    # children are contained in the parent interval
    o, i1, i2 = by_name["outer"], by_name["inner"], by_name["inner2"]
    assert o.ts_us <= i1.ts_us <= i2.ts_us
    assert i1.ts_us + i1.dur_us <= i2.ts_us + 1e-3
    assert i2.ts_us + i2.dur_us <= o.ts_us + o.dur_us + 1e-3


def test_span_block_waits_for_designated_outputs(monkeypatch):
    blocked = []

    real = jax.block_until_ready

    def spy(x):
        blocked.append(x)
        time.sleep(0.02)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", spy)
    tr = telemetry.Tracer()
    x = jnp.arange(4)
    with tr.span("work", block=True) as sp:
        assert sp.block_on(x) is x
    assert blocked and blocked[0] == [x]
    # the injected sync happened BEFORE the clock stopped
    assert tr.events[0].dur_us >= 0.02 * 1e6
    # block=False never syncs
    blocked.clear()
    with tr.span("nowait") as sp:
        sp.block_on(x)
    assert blocked == []


def test_traced_decorator_late_binds_active_tracer():
    @telemetry.traced("double", block=True)
    def double(x):
        return x * 2

    out = double(jnp.arange(3))          # telemetry off: still works
    assert list(np.asarray(out)) == [0, 2, 4]
    with telemetry.tracing() as tr:
        double(jnp.arange(3))
    assert [e.name for e in tr.events] == ["double"]


def test_tracing_scope_installs_and_restores():
    assert not telemetry.get_tracer().enabled
    with telemetry.tracing() as tr:
        assert telemetry.get_tracer() is tr
        with telemetry.span("s"):
            pass
    assert not telemetry.get_tracer().enabled
    assert [e.name for e in tr.events] == ["s"]


def test_null_tracer_is_shared_noop_and_cheap():
    s1 = telemetry.span("a")
    s2 = telemetry.span("b", block=True)
    assert s1 is s2                      # one shared handle, no allocation
    x = object()
    with s1 as sp:
        assert sp.block_on(x) is x
        sp.set(ignored=1)
    # micro-benchmark: the acceptance bar is "no measurable overhead";
    # 10us/span is orders of magnitude above the real cost and far below
    # any stage duration
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with telemetry.span("hot"):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 10e-6, f"null span costs {per_span*1e6:.2f}us"


def test_stopwatch_times_without_tracer_and_records_with():
    with telemetry.stopwatch("w") as sw:
        time.sleep(0.01)
    assert sw.dur_s >= 0.01              # times even with telemetry off
    with telemetry.tracing() as tr:
        with telemetry.stopwatch("w2"):
            pass
    assert [e.name for e in tr.events] == ["w2"]


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_typed_get_or_create():
    m = telemetry.MetricsRegistry()
    c = m.counter("moved", unit="bytes")
    assert m.counter("moved") is c
    c.inc(3)
    c.inc(4)
    assert c.value == 7
    with pytest.raises(ValueError):
        c.inc(-1)
    g = m.gauge("imb")
    g.set(1.25)
    with pytest.raises(TypeError):
        m.gauge("moved")
    with pytest.raises(TypeError):
        m.counter("imb")
    assert m.snapshot() == {"imb": 1.25, "moved": 7}
    m.tick(0)
    m.tick(1, ts_us=5.0)
    assert m.summary()["n_ticks"] == 2
    assert m.ticks[1]["values"] == {"imb": 1.25, "moved": 7}


# ---------------------------------------------------------------------------
# Async-dispatch timing regression (satellite 1)
# ---------------------------------------------------------------------------

def test_balance_timed_blocks_on_sharded_result(monkeypatch):
    """balance_timed must not stop the clock at dispatch: with a sync
    that takes >= dt injected, the reported wall-time is >= dt."""
    dt = 0.05
    real = jax.block_until_ready

    def slow_block(x):
        time.sleep(dt)
        return real(x)

    bal = Balancer.from_spec(
        BalanceSpec(p=8, method="hsfc", backend="sharded"))
    w = jnp.ones(256)
    xyz = _coords(256)
    bal.balance_timed(w, coords=xyz)     # warm up: compile outside timing
    monkeypatch.setattr(jax, "block_until_ready", slow_block)
    _, t = bal.balance_timed(w, coords=xyz)
    assert t["t_balance"] >= dt


def test_session_stage_times_cover_block(monkeypatch):
    """Every StepStats stage timing is a blocking measurement: inject a
    slow sync and the recorded stage wall-times must absorb it."""
    dt = 0.01
    real = jax.block_until_ready

    def slow_block(x):
        time.sleep(dt)
        return real(x)

    spec = AdaptSpec(problem="helmholtz", max_steps=1, max_tets=500,
                     backend="sharded",
                     balance=BalanceSpec(p=8, method="hsfc",
                                         backend="sharded"))
    mesh = cylinder_mesh(4, 2, length=3.0, radius=0.5)
    monkeypatch.setattr(jax, "block_until_ready", slow_block)
    res = AdaptiveSession(spec).run(mesh)
    st = res.stats[0]
    for t in (st.t_solve, st.t_estimate, st.t_balance):
        assert t >= dt


# ---------------------------------------------------------------------------
# Exporters (satellite 3)
# ---------------------------------------------------------------------------

def _traced_session(backend="host", seed_mesh=None, max_steps=2):
    spec = AdaptSpec(problem="helmholtz", max_steps=max_steps, max_tets=800,
                     backend=backend,
                     balance=BalanceSpec(p=8, method="hsfc",
                                         backend=backend))
    mesh = seed_mesh or cylinder_mesh(4, 2, length=3.0, radius=0.5)
    with telemetry.tracing() as tr:
        AdaptiveSession(spec).run(mesh)
    return tr


def test_chrome_trace_schema_and_roundtrip(tmp_path):
    tr = _traced_session()
    path = tmp_path / "trace.json"
    doc = telemetry.export_chrome_trace(tr, str(path))
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"]
    xs = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
    assert {"adapt/step", "adapt/solve", "balance"} <= {e["name"]
                                                        for e in xs}
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
    cs = [e for e in loaded["traceEvents"] if e["ph"] == "C"]
    assert {"imbalance", "cut"} <= {e["name"] for e in cs}
    # the validator actually rejects broken documents
    bad = {"traceEvents": [dict(doc["traceEvents"][2], dur=-1.0)]}
    with pytest.raises(texport.SchemaError):
        texport.validate_chrome_trace(bad)
    with pytest.raises(texport.SchemaError):
        texport.validate_chrome_trace({"events": []})
    # non-monotonic ts
    ev = dict(ph="X", name="a", ts=100.0, dur=1.0, args={})
    ev2 = dict(ph="X", name="b", ts=5.0, dur=1.0, args={})
    with pytest.raises(texport.SchemaError):
        texport.validate_chrome_trace({"traceEvents": [ev, ev2]})
    # overlapping-but-not-nested spans
    ev3 = dict(ph="X", name="c", ts=100.5, dur=200.0, args={})
    with pytest.raises(texport.SchemaError):
        texport.validate_chrome_trace({"traceEvents": [ev, ev3]})


def test_jsonl_schema_and_determinism(tmp_path):
    tr = _traced_session()
    path = tmp_path / "ev.jsonl"
    lines = telemetry.export_jsonl(tr, str(path))
    parsed = [json.loads(line) for line in path.read_text().splitlines()]
    assert parsed[0]["type"] == "meta"
    assert parsed[-1]["type"] == "totals"
    texport.validate_jsonl(parsed)
    # counter totals are deterministic across repeated seeded runs --
    # compare the final totals lines byte-for-byte
    tr2 = _traced_session()
    t1 = json.dumps(lines[-1], sort_keys=True)
    t2 = json.dumps(telemetry.jsonl_events(tr2)[-1], sort_keys=True)
    assert t1 == t2
    with pytest.raises(texport.SchemaError):
        texport.validate_jsonl(parsed[:-1])     # totals line missing
    with pytest.raises(texport.SchemaError):
        texport.validate_jsonl(parsed[1:])      # meta header missing


def test_label_gauges_export_as_labels(tmp_path):
    """A gauge holding a label (the matvec's chosen path) rides in the
    JSONL counters and totals, and gets no Chrome counter track."""
    tr = telemetry.Tracer()
    tr.metrics.gauge("fem_matvec.path").set("window")
    tr.metrics.gauge("cut").set(3)
    tr.tick(0)
    doc = telemetry.export_chrome_trace(tr, str(tmp_path / "trace.json"))
    assert {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"} == \
        {"cut"}
    lines = telemetry.export_jsonl(tr, str(tmp_path / "ev.jsonl"))
    counters = [ln for ln in lines if ln["type"] == "counters"]
    assert counters[0]["values"] == {"cut": 3, "fem_matvec.path": "window"}
    assert lines[-1]["metrics"]["totals"]["fem_matvec.path"] == "window"
    counters[0]["values"]["cut"] = [3]
    with pytest.raises(texport.SchemaError):
        texport.validate_jsonl(lines)


def test_quality_counters_bit_identical_host_vs_sharded():
    """The quality counters come from one publication site fed by
    bit-exact pipelines: identical inputs => identical totals dicts."""
    from repro.core.metrics import cut_links
    n, p = 512, 8
    w = jnp.asarray(np.random.RandomState(3).randint(1, 5, n), jnp.float32)
    xyz = _coords(n, seed=3)
    old = jnp.asarray(np.random.RandomState(4).randint(0, p, n), jnp.int32)
    adj = jnp.asarray(
        np.stack([np.arange(n), np.roll(np.arange(n), 1)], 1))
    totals = {}
    for backend in ("host", "sharded"):
        bal = Balancer.from_spec(
            BalanceSpec(p=p, method="hsfc", backend=backend))
        with telemetry.tracing() as tr:
            res = bal.balance(w, coords=xyz, old_parts=old)
            tr.metrics.gauge("cut").set(
                int(cut_links(res.parts, adj)))
        totals[backend] = tr.metrics.summary()["totals"]
    assert totals["host"] == totals["sharded"]


# ---------------------------------------------------------------------------
# Session + serve integration
# ---------------------------------------------------------------------------

def test_session_publishes_quality_counters_and_hooks_still_fire():
    stages, steps = [], []
    spec = AdaptSpec(problem="helmholtz", max_steps=2, max_tets=800,
                     balance=BalanceSpec(p=8, method="hsfc"))
    mesh = cylinder_mesh(4, 2, length=3.0, radius=0.5)
    with telemetry.tracing() as tr:
        res = AdaptiveSession(
            spec,
            on_stage=lambda s, v, dt: stages.append((s, dt)),
            on_step=lambda st, state: steps.append(st)).run(mesh)
    totals = tr.metrics.summary()["totals"]
    assert {"imbalance", "cut", "migration_total_v",
            "migration_retained", "repartitions"} <= set(totals)
    assert len(tr.metrics.ticks) == len(res.stats)
    # hooks remain thin adapters: same count/values as StepStats
    assert len(steps) == len(res.stats)
    assert all(dt >= 0 for _, dt in stages)
    names = {e.name for e in tr.events}
    assert {"adapt/step", "adapt/solve", "adapt/estimate",
            "adapt/balance", "balance"} <= names
    # StepStats consumers keep working unchanged
    assert res.stats[0].t_solve > 0
    # per-session tracer kwarg: spans land without an ambient scope
    tr2 = telemetry.Tracer()
    AdaptiveSession(spec, tracer=tr2).run(
        cylinder_mesh(4, 2, length=3.0, radius=0.5))
    assert {e.name for e in tr2.events} >= {"adapt/step", "balance"}
    assert not telemetry.get_tracer().enabled


@pytest.mark.slow
def test_serve_trace_spans_and_moved_kv_counter():
    from repro.configs import get_smoke
    from repro.models import init_model
    from repro.serve import ServeSession, ServeSpec, bursty_trace, run_trace

    cfg = get_smoke("llama3_8b").replace(n_layers=2, d_model=128, n_heads=4,
                                         n_kv_heads=2, head_dim=32,
                                         d_ff=256)
    params = init_model(cfg, jax.random.PRNGKey(0))
    groups = min(4, len(jax.devices()))
    spec = ServeSpec(slots=2 * groups, groups=groups, max_seq=64,
                     rebalance_every=4, prefill="full", decode="sharded",
                     rebalance="kv",
                     balance=BalanceSpec(p=groups, method="linear",
                                         oneD="ksection", warm_start=True))
    session = ServeSession(params, cfg, spec)
    trace = bursty_trace(12, seed=0, vocab=cfg.vocab,
                         prompt_buckets=(4, 8), max_new_cap=12)
    with telemetry.tracing() as tr:
        metrics = run_trace(session, trace, max_steps=150)
    names = {e.name for e in tr.events}
    assert {"serve/run_trace", "serve/prefill", "serve/decode"} <= names
    totals = tr.metrics.summary()["totals"]
    # counter total equals the migration_log the engine already keeps
    assert totals["moved_kv_bytes"] == metrics["moved_kv_bytes_total"]


# ---------------------------------------------------------------------------
# Profiler clock, span parents and compile events
# ---------------------------------------------------------------------------

def _host_span_names(trace_dir):
    """Names of the events on the host planes of the profile under
    ``trace_dir``."""
    import glob
    import os
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = ProfileData.from_file(path)
    return {e.name for plane in data.planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events}


def test_tracer_spans_reach_the_profiler_host_plane(tmp_path):
    f = jax.jit(lambda x: x * 2)
    x = jnp.arange(8.0)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with telemetry.span("test/null_span"):
            f(x).block_until_ready()
        with telemetry.tracing():
            with telemetry.span("test/outer"):
                with telemetry.stopwatch("test/stopwatch"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = _host_span_names(str(tmp_path))
    assert {"test/outer", "test/stopwatch"} <= names
    # telemetry off: the null span leaves nothing in the profile
    assert "test/null_span" not in names


def test_span_events_name_their_parent(tmp_path):
    tr = telemetry.Tracer()
    with telemetry.tracing(tr):
        with telemetry.span("outer", step=4):
            with telemetry.stopwatch("inner"):
                pass
            with telemetry.span("sibling"):
                pass
    parents = {e.name: e.parent for e in tr.events}
    assert parents == {"outer": None, "inner": "outer", "sibling": "outer"}
    lines = telemetry.export_jsonl(tr, str(tmp_path / "ev.jsonl"))
    assert {ln["name"]: ln["parent"] for ln in lines
            if ln["type"] == "span"} == parents


def test_jit_traces_count_fresh_closures_per_call_and_cached_once():
    """A fresh ``jax.jit`` closure traces on every call, a cached one
    once; a jitted helper traced inside a program is not a program."""
    @jax.jit
    def cached_prog(x):
        return jnp.sin(x) + 1.0

    def fresh():
        @jax.jit
        def fresh_prog(x):
            return jnp.cos(x) * 2.0
        return fresh_prog

    x = jnp.arange(5.0)
    with telemetry.tracing() as tr:
        for step in range(3):
            with telemetry.span("test/call", step=step):
                fresh()(x).block_until_ready()
                cached_prog(x).block_until_ready()
    traces = [e for e in tr.jit_events if e.kind == "jit_traces"]
    by_prog = {}
    for e in traces:
        by_prog.setdefault(e.program, []).append(e)
    assert len(by_prog["jit_fresh_prog"]) == 3
    assert len(by_prog["jit_cached_prog"]) == 1
    assert "jit_cos" not in by_prog and "jit_sin" not in by_prog
    assert [e.step for e in by_prog["jit_fresh_prog"]] == [0, 1, 2]
    assert all(e.span == "test/call" for e in traces)
    summary = tr.jit_summary()
    assert set(summary) == set(telemetry.JIT_KINDS)
    assert summary["jit_traces"]["jit_fresh_prog"]["n"] == 3
    assert summary["jit_lowerings"]["jit_fresh_prog"]["n"] == 3
    assert summary["jit_compiles"]["jit_cached_prog"]["n"] == 1
    assert summary["jit_traces"]["jit_cached_prog"]["s"] > 0


def test_compile_events_are_heard_only_while_tracing():
    x = jnp.ones(3)
    x.block_until_ready()
    tr = telemetry.Tracer()

    def kinds():
        return sorted(e.kind for e in tr.jit_events)

    with telemetry.tracing(tr):
        with telemetry.tracing(tr):          # nested: heard once, not twice
            jax.jit(lambda v: v + 3.0)(x)
        assert kinds() == ["jit_compiles", "jit_lowerings", "jit_traces"]
        jax.jit(lambda v: v + 4.0)(x)        # the outer scope still hears
        assert len(tr.jit_events) == 6
    jax.jit(lambda v: v + 5.0)(x)
    assert len(tr.jit_events) == 6           # nothing heard after the scope
    null = telemetry.NullTracer()
    with telemetry.tracing(null):
        jax.jit(lambda v: v + 6.0)(x)
    assert not hasattr(null, "jit_events")   # the null tracer hears nothing


def test_persistent_cache_loads_count_as_cache_hits(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        def fresh():
            @jax.jit
            def cache_prog(x):
                return jnp.tanh(x) * 3.0 + 0.25
            return cache_prog

        x = jnp.arange(7.0)
        with telemetry.tracing() as tr:
            fresh()(x).block_until_ready()      # compiled, written
            jax.clear_caches()
            fresh()(x).block_until_ready()      # loaded from the cache
        summary = tr.jit_summary()
        assert summary["jit_compiles"]["jit_cache_prog"]["n"] == 1
        assert summary["jit_cache_hits"]["jit_cache_prog"]["n"] == 1
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


OWNED_SPANS = {
    "adapt/elements", "adapt/solve/assemble", "adapt/pack",
    "adapt/pack/halo_plan", "adapt/pack/migrate", "adapt/pack/cut",
    "adapt/solve/operators", "adapt/solve/pcg", "adapt/solve/wait",
    "adapt/l2_error", "adapt/emit_stats",
}


def _owned_session(max_steps=2):
    spec = AdaptSpec(problem="helmholtz", max_steps=max_steps,
                     max_tets=5000, backend="sharded",
                     vertex_layout="owned", incremental=True,
                     balance=BalanceSpec(p=1, method="hsfc",
                                         oneD="ksection"))
    return AdaptiveSession(spec, devices=jax.devices()[:1])


def test_owned_session_spans_carry_step_and_parent():
    tr = telemetry.Tracer()
    with telemetry.tracing(tr):
        res = _owned_session().run(cylinder_mesh(4, 2, length=3.0,
                                                 radius=0.5))
    names = {e.name for e in tr.events}
    assert OWNED_SPANS <= names
    assert not any(n.startswith("fem/matvec") for n in names)
    session_spans = [e for e in tr.events if e.name.startswith("adapt/")]
    assert all(e.attrs.get("step") in range(len(res.stats))
               for e in session_spans)
    parents = {(e.name, e.parent) for e in tr.events}
    for child in ("halo_plan", "migrate", "cut"):
        assert (f"adapt/pack/{child}", "adapt/pack") in parents
    for child in ("assemble", "operators", "pcg", "wait"):
        assert (f"adapt/solve/{child}", "adapt/solve") in parents
    # step 0 packs inside its solve stage; the refined mesh repacks in
    # the balance stage
    assert ("adapt/pack", "adapt/solve") in parents
    assert ("adapt/pack", "adapt/balance") in parents
    for name in ("adapt/elements", "adapt/l2_error"):
        assert (name, "adapt/step") in parents
    assert ("adapt/emit_stats", None) in parents
    # every compile event lands under some span of the step
    assert tr.jit_events
    assert all(e.span is not None and e.step is not None
               for e in tr.jit_events)
    pcg = {e.program for e in tr.jit_events if e.span == "adapt/solve/pcg"}
    assert "jit_solve" in pcg


def test_tracer_runs_no_program_the_untraced_session_does_not():
    """Under a Tracer the owned session lowers the same programs as
    without one: tracing adds no device work."""
    lowered = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(kw["fun_name"])

    def programs(traced):
        jax.clear_caches()
        lowered.clear()
        mesh = cylinder_mesh(4, 2, length=3.0, radius=0.5)
        if traced:
            with telemetry.tracing():
                _owned_session().run(mesh)
        else:
            _owned_session().run(mesh)
        return sorted(lowered)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        programs(False)                       # warm imports and host caches
        plain, traced = programs(False), programs(True)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert plain and plain == traced
    assert "jit(balance)" in plain and "jit(pack_elements)" in plain


def _owned_packing(n_axial=80):
    from repro.fem import build_elements, build_halo_plan
    from repro.fem.parallel import device_mesh, shard_elements
    m = cylinder_mesh(n_axial, 2, length=4.0, radius=0.5)
    el = build_elements(m.verts, m.tets)
    parts = np.zeros(m.n_tets, np.int64)
    plan = build_halo_plan(m.tets, parts, m.n_verts, 1)
    return shard_elements(el, parts, 1, halo=plan), device_mesh(1)


@pytest.mark.parametrize("path", ["window", "oracle"])
def test_owned_operators_set_matvec_gauges_under_a_tracer(path):
    """Building owned operators publishes which matvec engaged, its
    window, the widest tile and the ordering's host seconds; the oracle
    path orders nothing."""
    from repro.fem.parallel import make_owned_operators
    sel, jm = _owned_packing()
    tr = telemetry.Tracer()
    with telemetry.tracing(tr):
        make_owned_operators(sel, jm, 1.0, use_pallas=path == "window",
                             interpret=True)
    got = {k: v for k, v in tr.metrics.snapshot().items()
           if k.startswith("fem_matvec.")}
    assert set(got) == {"fem_matvec.path", "fem_matvec.window_slots",
                        "fem_matvec.tile_span_max", "fem_matvec.order_s"}
    assert got["fem_matvec.path"] == path
    if path == "window":
        w = got["fem_matvec.window_slots"]
        assert w % 128 == 0 and 0 < w < sel.halo.V
        assert 0 < got["fem_matvec.tile_span_max"] <= w
        assert got["fem_matvec.order_s"] > 0
    else:
        assert got["fem_matvec.window_slots"] == 0
        assert got["fem_matvec.tile_span_max"] == 0
        assert got["fem_matvec.order_s"] == 0


def test_owned_operators_set_no_gauge_without_a_tracer():
    from repro.fem.parallel import make_owned_operators
    sel, jm = _owned_packing()
    idle = telemetry.Tracer()                 # made, never installed
    assert not telemetry.get_tracer().enabled
    make_owned_operators(sel, jm, 1.0, use_pallas=True, interpret=True)
    assert idle.metrics.names() == []
    assert telemetry.get_tracer().metrics.names() == []
