#!/usr/bin/env python3
"""Smoke run of the adaptive-FEM + dynamic-load-balancer path on a TPU.

    python chip_smoke.py              # one chip: phases kernels, adapt, balance
    python chip_smoke.py --chips 4    # four chips: phase adapt at p = 4 only

Phases, all in this one process:

* ``kernels``  -- ``sfc_keys_op``, ``ksection_histogram_op`` and
  ``fem_matvec_op`` run compiled at sizes where each dispatch picks the
  Pallas kernel, each checked against its ``kernels/ref.py`` oracle:
  bit-exact for keys and histograms (integer weights), within
  ``MATVEC_RTOL`` of the largest entry for the matvec.
* ``adapt``    -- ``AdaptiveSession`` on the paper's Helmholtz problem
  (Example 3.1) with the sharded balancer, owned vertices, halo-exchange
  PCG and incremental rebalancing, from a cylinder mesh of 518,400 tets,
  for ``ADAPT_STEPS`` steps.  On the final mesh it checks the owned PCG
  solution against the replicated-layout PCG oracle
  (``fem.solve.solve_dirichlet``) within ``SOLVE_RTOL`` of max |u|, and
  the sharded balancer's parts against the host balancer's, bit for bit,
  on the same weights and coordinates.
* ``balance``  -- a 64-part ``Balancer`` on the final mesh's element
  centroids and error-indicator weights: weight conserved, no part
  empty, imbalance at most ``BALANCE_IMBALANCE``.

Each phase prints one JSON line with its wall and compile seconds; the
last line is ``{"ok": true, "device": {...}}``.  Any failed check raises,
so the script exits non-zero and prints no such line.  It refuses to run
(non-zero exit, no result) when JAX's first device is not a TPU.

Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR`` says,
else in ``.jax_cache/`` beside this file.  The script uses the devices
JAX finds; it sets no forced host-device count.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# helmholtz on cylinder_mesh(600, 12, length=4, radius=0.5): 518,400 tets
MESH = dict(n_axial=600, n_cross=12, length=4.0, radius=0.5)
ADAPT_STEPS = 3
MAX_TETS = 4_000_000          # no ceiling within ADAPT_STEPS refinements
BALANCE_PARTS = 64
N_ITEMS = 1 << 20             # keys / histogram items in phase kernels
N_CUTS = 252                  # (p - 1) * k candidates at p = 64, k = 4
MATVEC_RTOL = 1e-4            # kernel vs oracle, relative to max |y|
SOLVE_RTOL = 1e-4             # owned vs replicated PCG, relative to max |u|
BALANCE_IMBALANCE = 1.05


def use_compile_cache() -> None:
    """Persistent compile cache: JAX's own where the environment names
    one, else a fixed directory in the checkout (a path that moves
    between runs never hits)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))


class CompileClock:
    """Sums the backend-compile seconds JAX reports while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def check(ok: bool, what: str) -> None:
    """A phase's check; raises (unlike ``assert``, also under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _report(phase: str, t0: float, clock: CompileClock, c0: float,
            **fields) -> None:
    print(json.dumps({"phase": phase, "wall_s": time.perf_counter() - t0,
                      "compile_s": clock.seconds - c0, **fields}),
          flush=True)


def _check_kernel(fn, *args) -> None:
    """The jitted call lowers to a compiled Pallas kernel."""
    text = jax.jit(fn).lower(*args).as_text()
    check("tpu_custom_call" in text, "no compiled Pallas kernel")


def phase_kernels(seed: int) -> dict:
    from repro.kernels import ref
    from repro.kernels.fem_matvec import fem_matvec_max_verts
    from repro.kernels.ops import (fem_matvec_op, fem_matvec_path,
                                   ksection_histogram_op, sfc_keys_op)
    rng = np.random.default_rng(seed)
    n, m = N_ITEMS, N_CUTS
    out = {}

    grid = jnp.asarray(rng.integers(0, 1 << 10, (n, 3)), jnp.uint32)
    _check_kernel(sfc_keys_op, grid)
    keys = sfc_keys_op(grid)
    want = ref.hilbert_keys_ref(grid)
    check(np.array_equal(np.asarray(keys), np.asarray(want)), "sfc keys")
    out["sfc_keys"] = {"n": n, "bit_exact": True}

    kf = jnp.asarray(rng.random(n), jnp.float32)
    w = jnp.asarray(rng.integers(0, 10, n), jnp.float32)
    cuts = jnp.asarray(rng.random(m), jnp.float32)
    _check_kernel(ksection_histogram_op, kf, w, cuts)
    got = ksection_histogram_op(kf, w, cuts)
    want = ref.ksection_histogram_ref(kf, w, cuts)
    check(np.array_equal(np.asarray(got), np.asarray(want)), "histogram")
    out["ksection_hist"] = {"n": n, "m": m, "bit_exact": True}

    # the largest part the matvec size rule sends to the kernel
    v = fem_matvec_max_verts()
    c = 6 * v
    tets = jnp.asarray(rng.integers(0, v, (c, 4)), jnp.int32)
    grads = jnp.asarray(rng.standard_normal((c, 4, 3)), jnp.float32)
    vol = jnp.asarray(rng.random(c) + 0.1, jnp.float32)
    u = jnp.asarray(rng.standard_normal(v + 1), jnp.float32)
    path = fem_matvec_path(v)
    check(path == "window", f"matvec path {path}")

    def matvec(t, g, vl, x):
        return fem_matvec_op(t, g, vl, x, v, c=1.0)

    _check_kernel(matvec, tets, grads, vol, u)
    got = matvec(tets, grads, vol, u)
    want = ref.fem_matvec_ref(tets, grads, vol, u, v, c=1.0)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    check(err <= MATVEC_RTOL, f"matvec rel err {err}")
    out["fem_matvec"] = {"verts": v, "elements": c, "path": path,
                         "rel_err": err, "rtol": MATVEC_RTOL}
    return out


def phase_adapt(p: int, devices, clock: CompileClock) -> tuple:
    from repro.core import Balancer, BalanceSpec, resolve_variants
    from repro.fem import (AdaptiveSession, AdaptSpec, build_elements,
                           cylinder_mesh, load_vector, solve_dirichlet)
    from repro import telemetry

    t0 = time.perf_counter()
    mesh = cylinder_mesh(**MESH)
    t_mesh = time.perf_counter() - t0
    bspec = BalanceSpec(p=p, method="hsfc", oneD="ksection")
    spec = AdaptSpec.for_problem(
        "helmholtz", backend="sharded", vertex_layout="owned",
        incremental=True, balance=bspec, max_steps=ADAPT_STEPS,
        max_tets=MAX_TETS)
    last = {}
    mark = [clock.seconds]

    def on_stage(stage, variant, dt):
        # one line per stage as it ends: a run cut short still shows
        # where its time went
        print(json.dumps({"stage": stage, "variant": variant, "wall_s": dt,
                          "compile_s": clock.seconds - mark[0]}), flush=True)
        mark[0] = clock.seconds

    def on_step(stats, state):
        last.update(eta=state.eta)
        print(json.dumps({"step": state.step, "n_tets": stats.n_tets,
                          "n_verts": stats.n_verts,
                          "cg_iters": stats.cg_iters}), flush=True)

    session = AdaptiveSession(spec, devices=devices, on_step=on_step,
                              on_stage=on_stage)
    # the tracer keeps the matvec's gauges of the last operator build
    with telemetry.tracing() as tr:
        res = session.run(mesh)
    final = res.mesh
    info = {
        "p": p, "mesh_build_s": t_mesh,
        "steps": [{"n_tets": s.n_tets, "n_verts": s.n_verts,
                   "cg_iters": s.cg_iters, "t_solve_s": s.t_solve,
                   "t_balance_s": s.t_balance, "imbalance": s.imbalance,
                   "err_l2": s.err_l2} for s in res.stats],
        "balance_variants": resolve_variants(session.balance_spec),
        "adapt_variants": session.variants,
        "matvec": {k: v for k, v in tr.metrics.snapshot().items()
                   if k.startswith("fem_matvec.")},
        "part_verts": res.halo.V,
    }
    # the owned packing is spread over all p devices
    for x in (res.sharded.tets, res.sharded.grads, res.sharded.vol):
        check(len(x.sharding.device_set) == p, f"packing on {x.sharding}")

    # owned halo-exchange PCG vs the replicated-layout PCG oracle
    prob = session.problem
    verts = jnp.asarray(final.verts)
    el = build_elements(final.verts, final.tets)
    free = np.ones(final.n_verts)
    free[final.boundary_vertices()] = 0.0
    ref = solve_dirichlet(el, load_vector(el, verts, prob.f),
                          prob.exact(verts), jnp.asarray(free), prob.c,
                          tol=spec.tol, maxiter=spec.maxiter)
    u_ref = np.asarray(ref.x)
    gap = float(np.max(np.abs(np.asarray(res.u) - u_ref))
                / np.max(np.abs(u_ref)))
    check(gap <= SOLVE_RTOL, f"owned vs replicated PCG gap {gap}")
    info["solve_check"] = {"rel_gap": gap, "rtol": SOLVE_RTOL,
                           "oracle_cg_iters": int(ref.iters)}

    # sharded balancer vs host balancer on the same weights and coordinates
    # (the payload executor does not touch the parts, and its compile
    # dominates the pipeline's on the chip: leave it out here)
    w = jnp.ones(final.n_tets, jnp.float32)
    coords = jnp.asarray(final.barycenters())
    old = jnp.asarray(final.leaf_payload["parts"])
    sharded = Balancer(bspec.replace(backend="sharded",
                                     execute_migration=False),
                       devices=devices)
    host = Balancer(bspec.replace(backend="host"))
    a = np.asarray(sharded.balance(w, coords=coords, old_parts=old).parts)
    b = np.asarray(host.balance(w, coords=coords, old_parts=old).parts)
    check(np.array_equal(a, b), "sharded vs host parts")
    info["parts_check"] = {"bit_identical": True, "n": int(a.size)}
    return info, final, last["eta"]


def phase_balance(final, eta, p: int = BALANCE_PARTS) -> dict:
    from repro.core import Balancer, BalanceSpec
    bal = Balancer(BalanceSpec(p=p, method="hsfc", oneD="ksection"))
    w = np.asarray(eta, np.float32)
    r = bal.balance(jnp.asarray(w), coords=jnp.asarray(final.barycenters()))
    parts = np.asarray(r.parts)
    check(parts.shape == w.shape and parts.min() >= 0 and parts.max() < p,
          "part ids")
    w64 = w.astype(np.float64)
    pw = np.bincount(parts, weights=w64, minlength=p)
    check(np.isclose(pw.sum(), w64.sum(), rtol=1e-12), "weight lost")
    check(np.allclose(np.asarray(r.part_weights), pw, rtol=1e-4),
          "part weights")
    counts = np.bincount(parts, minlength=p)
    check((counts > 0).all(), "empty part")
    imb = float(pw.max() / pw.mean())
    check(imb <= BALANCE_IMBALANCE, f"imbalance {imb}")
    return {"p": p, "n": int(parts.size), "imbalance": imb,
            "min_items": int(counts.min()), "max_items": int(counts.max())}


def _peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return "not measured"
    return [int(s["peak_bytes_in_use"]) for s in stats]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the adapt phase at p = 4")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the phase-kernels data")
    args = ap.parse_args(argv)

    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); refusing to run", file=sys.stderr)
        return 1
    import repro  # noqa: F401  (fails here, before any phase, outside the repo)
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    clock = CompileClock()

    if args.chips == 1:
        t0, c0 = time.perf_counter(), clock.seconds
        _report("kernels", t0, clock, c0, **phase_kernels(args.seed))
    t0, c0 = time.perf_counter(), clock.seconds
    info, final, eta = phase_adapt(args.chips, devices, clock)
    _report("adapt", t0, clock, c0, **info)
    if args.chips == 1:
        t0, c0 = time.perf_counter(), clock.seconds
        _report("balance", t0, clock, c0, **phase_balance(final, eta))
    print(json.dumps({"peak_bytes_in_use": _peak_bytes(devices)}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
