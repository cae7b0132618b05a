"""Telemetry smoke: one command, one trace covering adapt + serve.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.telemetry.smoke --out DIR

Runs a 3-step adaptive session (sharded backend, owned vertices) and a
short serve trace (sharded decode, KV rebalancing) under ONE tracer,
then exports ``DIR/trace.json`` (Chrome-trace, load in Perfetto) and
``DIR/counters.jsonl``, validates both against their schemas, and
asserts the trace contains a span for every registered stage and layer
boundary inside the step, a counter for each of the paper's quality
metrics, and the programs JAX traced under the tracer.  Non-zero exit on any
missing span/counter or schema violation — CI runs this as the
``telemetry-smoke`` job.
"""
import argparse
import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    # must be set before the first jax import for the sharded backends
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

# spans expected from the adaptive session + balancer ("adapt/solve" and
# "adapt/adapt_mesh" only appear for the stages the spec registers; the
# smoke spec below exercises all of them, and its owned layout the
# packing and distributed-PCG spans inside them) and from the serve engine
REQUIRED_SPANS = {
    "adapt/solve", "adapt/estimate", "adapt/mark", "adapt/adapt_mesh",
    "adapt/balance", "balance",
    "adapt/elements", "adapt/l2_error", "adapt/emit_stats",
    "adapt/solve/assemble", "adapt/solve/operators", "adapt/solve/pcg",
    "adapt/solve/wait", "adapt/pack", "adapt/pack/halo_plan",
    "adapt/pack/migrate", "adapt/pack/cut",
    "serve/prefill", "serve/decode", "serve/rebalance", "serve/run_trace",
}
REQUIRED_COUNTERS = {
    "imbalance", "cut", "migration_total_v", "migration_retained",
    "comm_halo_bytes", "comm_psum_bytes", "moved_kv_bytes",
}


def _run_adaptive() -> None:
    import jax
    from repro.core import BalanceSpec
    from repro.fem import AdaptSpec, AdaptiveSession, cylinder_mesh

    p = min(8, jax.device_count())
    spec = AdaptSpec(
        problem="helmholtz", max_steps=3, max_tets=3000,
        backend="sharded", vertex_layout="owned",
        balance=BalanceSpec(p=p, method="hsfc", backend="sharded"))
    mesh = cylinder_mesh(6, 2, length=3.0, radius=0.5)
    AdaptiveSession(spec).run(mesh)


def _run_serve() -> None:
    import jax
    from repro.configs import get_smoke
    from repro.core import BalanceSpec
    from repro.models import init_model
    from repro.serve import ServeSession, ServeSpec, bursty_trace, run_trace

    cfg = get_smoke("llama3_8b").replace(n_layers=2, d_model=128, n_heads=4,
                                         n_kv_heads=2, head_dim=32, d_ff=256)
    params = init_model(cfg, jax.random.PRNGKey(0))
    groups = min(4, len(jax.devices()))
    spec = ServeSpec(
        slots=2 * groups, groups=groups, max_seq=64, rebalance_every=4,
        prefill="full", decode="sharded", rebalance="kv",
        balance=BalanceSpec(p=groups, method="linear", oneD="ksection",
                            warm_start=True))
    session = ServeSession(params, cfg, spec)
    trace = bursty_trace(16, seed=0, vocab=cfg.vocab,
                         prompt_buckets=(4, 8, 16), max_new_cap=16)
    run_trace(session, trace, max_steps=200)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="telemetry_smoke",
                    help="output directory for trace.json/counters.jsonl")
    args = ap.parse_args(argv)

    from repro import telemetry

    os.makedirs(args.out, exist_ok=True)
    with telemetry.tracing() as tr:
        _run_adaptive()
        _run_serve()

    trace_path = os.path.join(args.out, "trace.json")
    jsonl_path = os.path.join(args.out, "counters.jsonl")
    # export_* validate against the schema before writing
    telemetry.export_chrome_trace(tr, trace_path)
    telemetry.export_jsonl(tr, jsonl_path)

    span_names = {ev.name for ev in tr.events}
    missing_spans = REQUIRED_SPANS - span_names
    totals = tr.metrics.summary()["totals"]
    missing_counters = REQUIRED_COUNTERS - set(totals)

    print(f"wrote {trace_path} ({len(tr.events)} spans) and {jsonl_path}")
    print("counter totals:", {k: totals[k] for k in sorted(totals)})
    ok = True
    if missing_spans:
        print(f"MISSING SPANS: {sorted(missing_spans)}", file=sys.stderr)
        ok = False
    if missing_counters:
        print(f"MISSING COUNTERS: {sorted(missing_counters)}",
              file=sys.stderr)
        ok = False
    if not any(e.kind == "jit_traces" for e in tr.jit_events):
        print("NO JIT EVENTS recorded under the tracer", file=sys.stderr)
        ok = False
    if ok:
        print("telemetry smoke OK")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
