"""Runs one cell of ``BENCHMARK.json`` and builds its result line.

Everything that belongs to one configuration, traffic mix, driver,
problem or per-layer metric is found by name in a file of its own:

- ``bench/configs/<config>.json`` (through the configuration's ``file``
  entry): the deployment, its sizes, the program's spec, the name of its
  problem and the limits of its checks;
- ``bench/traffic/<mix>.json``: the mix's numbers and the name of the
  driver that runs them;
- ``bench/drivers/<driver>.py``: ``Driver(config, mix, seed, devices, bench)``
  does the set-up; ``window(seconds, traced)`` measures and returns the
  record of the window; ``check()`` returns the numbers compared with
  the plain reference after the window (``check(lowp=True)``: the
  control's);
- ``bench/problems/<problem>.py``: the program's problem object and the
  reference's functions of the same problem;
- ``bench/metrics/<metric>.py``: ``read(rec) -> float | None``, where
  ``rec`` is the record of the window (see ``run_cell``) and ``None``
  means there was nothing to read, so the metric is left out of the line.
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent      # the checkout
BENCH = ROOT / "bench"
JIT_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(manifest: dict, name: str, root: Path = ROOT) -> dict:
    entry = find(manifest["configs"], name, "configuration")
    return json.loads((root / entry["file"]).read_text())


def load_mix(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def load_module(kind: str, name: str, bench: Path = BENCH):
    """``bench/<kind>/<name>.py`` as a module."""
    path = bench / kind / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no {path.relative_to(bench.parent)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, bench: Path = BENCH):
    return load_module("metrics", name, bench).read


def load_driver(name: str, bench: Path = BENCH):
    return load_module("drivers", name, bench).Driver


def load_problem(name: str, bench: Path = BENCH):
    return load_module("problems", name, bench)


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """The seed's generator; any whole number, however large, is a seed."""
    return np.random.default_rng([seed % (1 << 64), stream])


class Annotations:
    """Host spans in the profiler's trace (``bench/<what>``), opened and
    closed as the host moves from one activity to the next."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self._open = None

    def switch(self, what: str | None) -> None:
        if not self.enabled:
            return
        import jax
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        if what is not None:
            self._open = jax.profiler.TraceAnnotation(f"bench/{what}")
            self._open.__enter__()


def cell_metrics(manifest: dict, cell: str, kind: str) -> list:
    """The cell's ``end_to_end`` or ``per_layer`` metrics: those whose
    ``workloads`` list it; without that key, every end-to-end metric, and
    the per-layer metrics whose ``moves`` target the cell reports."""
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


class JitClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    its compile cache), with the host time of each event, from the
    installed JAX's own ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        self._m = jax.monitoring
        self.events = []                 # (perf_counter, name, seconds)
        self._m.register_event_duration_secs_listener(self._on_duration)
        self._m.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in JIT_EVENTS:
            self.events.append((time.perf_counter(), event, duration))

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.events.append((time.perf_counter(), event, 0.0))

    def between(self, t0: float, t1: float) -> tuple[float, int]:
        """(jit seconds, programs compiled rather than loaded) in [t0, t1]."""
        ev = [e for e in self.events if t0 <= e[0] <= t1]
        seconds = sum(d for _, n, d in ev if n != CACHE_HIT)
        compiles = (sum(n == JIT_EVENTS[2] for _, n, _ in ev)
                    - sum(n == CACHE_HIT for _, n, _ in ev))
        return seconds, compiles

    def close(self):
        self._m.unregister_event_duration_listener(self._on_duration)
        self._m.unregister_event_listener(self._on_event)


def use_compile_cache(root: Path = ROOT) -> None:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else a fixed directory in the checkout (a directory that moves
    between runs never hits).  Every program is cached, however small."""
    import os

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int, require_tpu: bool = True) -> list:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform!r}, "
                     "not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def peak_memory(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, bench: Path = BENCH,
             require_tpu: bool = True, control: bool = False) -> dict:
    """Set up, measure, check; return the result line as a dict.

    ``control``: also compute the numbers of the control (the reference
    at the precision below the configuration's, and the partition's
    control) on the same inputs, under ``control_checks``."""
    from bench import trace as trace_mod
    import jax
    manifest = load_manifest(root)
    cell = find(manifest["workloads"], workload, "workload")
    config = load_config(manifest, cell["config"], root)
    mix = load_mix(cell["traffic"], bench)
    devices = chips(cell["chips"], require_tpu)
    peak = peak_row(devices[0].device_kind, bench) if require_tpu else None
    clock = JitClock()
    try:
        t0 = time.perf_counter()
        drv = load_driver(mix["driver"], bench)(config, mix, seed, devices,
                                                bench)
        setup_s = time.perf_counter() - t0
        trace_dir = root / ".bench_trace" / workload
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            # no Python tracer: it would record every Python call and slow
            # the host work the window measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                rec = drv.window(seconds, trace)
        finally:
            if trace:
                jax.profiler.stop_trace()
        rec["jit_s"], rec["compiles"] = clock.between(rec["t_start"],
                                                      rec["t_end"])
    finally:
        clock.close()
    memory = peak_memory(devices)
    rec["trace"] = None
    if trace:
        rec["trace"] = trace_mod.reduce_trace(
            trace_mod.events_from_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec["peak"] = peak
    limits = config["limits"]
    numbers = drv.check()
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    rec["e2e"]["setup_s"] = setup_s
    if trace:
        metrics = {}
        for m in cell_metrics(manifest, workload, "per_layer"):
            value = load_reader(m["name"], bench)(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": rec["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell_metrics(manifest, workload, "end_to_end")}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": ok and rec["attempted"] > 0,
           "attempted": rec["attempted"], "failed": 0,
           "metrics": metrics, "device": device,
           "window": {"seconds": rec["t_end"] - rec["t_start"],
                      "compiles": rec["compiles"], "jit_s": rec["jit_s"]}}
    if trace and rec["trace"] is not None:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    if control:
        out["control_checks"] = drv.check(lowp=True)
    out["checks"] = checks
    return out


def peak_row(kind: str, bench: Path = BENCH) -> dict:
    table = json.loads((bench / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no row in bench/peaks.json")
    return table[kind]


def print_result(out: dict) -> None:
    for name, c in out["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILS"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
