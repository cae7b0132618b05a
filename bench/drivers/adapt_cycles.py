"""A closed loop of adaptive cycles.

Each cycle copies the initial mesh, builds a fresh ``AdaptiveSession`` and
runs ``steps_per_cycle`` steps of it: solve, estimate, mark, refine,
rebalance; the session leaves the last step's mesh unrefined, so that
its solution lives on the mesh it was solved on.  The configuration
names the problem (``bench/problems/<name>.py``) and gives the mesh, the
program's spec and the limits of the checks.  The seed picks the amplitude ``2**k`` of the
exact solution; scaling by a power of two is exact in floating point, so
every seed runs the same meshes and shapes on different values.

After the window, every step of one cycle drawn from the seed is compared
with the plain reference (``bench/reference``): the solution, the
estimate, the marking, the refinement and the parts.
"""
from __future__ import annotations

import copy
import time

import numpy as np

from bench import harness
from bench.reference import balance as ref_balance
from bench.reference import fem as ref_fem
from bench.reference.mesh import kuhn_cylinder


class Driver:
    # the stage after each one, so the trace names what the host does next
    NEXT_STAGE = {"solve": "estimate", "estimate": "error+mark",
                  "mark": "refine", "adapt_mesh": "balance"}

    def __init__(self, config: dict, mix: dict, seed: int, devices, bench):
        from repro.core import BalanceSpec
        from repro.fem import AdaptSpec, cylinder_mesh
        from repro.fem.problems import ProblemSetup, register_problem
        self.cfg, self.mix, self.devices = config, mix, devices
        prob = config["problem"]
        self.problem = harness.load_problem(prob["module"], bench)
        lo, hi = mix["amplitude_exponents"]
        self.exponent = int(harness.rng_for(seed).integers(lo, hi + 1))
        self.amplitude = 2.0 ** self.exponent
        self.rng_check = harness.rng_for(seed, 1)
        program = self.problem.program(self.amplitude, prob["c"])
        # one name for every amplitude: the name rides in the AdaptSpec,
        # which is static to the program's jitted functions, so a name per
        # amplitude would compile them all anew for each one
        name = config["name"]
        setup = ProblemSetup(name=name, kind="stationary",
                             make=lambda: program,
                             default_mesh=lambda: None, theta=prob["theta"])
        self._register = lambda: register_problem(setup)
        self._register()
        m = config["mesh"]
        self.mesh_args = (m["n_axial"], m["n_cross"], m["length"],
                          m["radius"])
        self.mesh0 = cylinder_mesh(m["n_axial"], m["n_cross"],
                                   length=m["length"], radius=m["radius"])
        self.v0, self.t0 = self.mesh0.verts.copy(), self.mesh0.tets.copy()
        s, b = config["spec"], config["balance"]
        self.spec = AdaptSpec.for_problem(
            name, backend=s["backend"], vertex_layout=s["vertex_layout"],
            incremental=s["incremental"],
            balance=BalanceSpec(p=len(devices), method=b["method"],
                                oneD=b["oneD"]),
            trigger=b["trigger"], imbalance_trigger=b["imbalance_trigger"],
            max_steps=mix["steps_per_cycle"], max_tets=s["max_tets"],
            theta=prob["theta"], tol=prob["tol"], maxiter=prob["maxiter"])
        self.ann = harness.Annotations(False)
        self.cycles = []
        self._cycle()                         # the warm cycle
        self.cycles = []

    def _cycle(self):
        from repro.fem import AdaptiveSession
        steps, caps, parts = [], [], []
        meshes = [(self.v0, self.t0)]
        ann = self.ann

        def on_stage(stage, variant, dt):
            steps[-1]["t_" + stage] = dt
            if stage == "solve":              # the parts the solve ran on
                parts.append(mesh.leaf_payload["parts"].copy())
            ann.switch(self.NEXT_STAGE.get(stage, "step"))

        def on_step(stats, state):
            caps.append({"u": state.u, "eta": state.eta,
                         "marked": state.marked, "parts": parts[-1]})
            steps[-1].update(
                cg_iters=stats.cg_iters, halo_bytes=stats.comm_halo_bytes,
                migrated_frac=stats.migration_totalv / stats.n_tets)
            if len(steps) < self.spec.max_steps:
                meshes.append((state.mesh.verts.copy(), state.mesh.tets))
                steps.append({})
            ann.switch("solve")

        steps.append({})
        ann.switch("setup_cycle")
        self._register()
        mesh = copy.deepcopy(self.mesh0)
        session = AdaptiveSession(self.spec, devices=self.devices,
                                  on_step=on_step, on_stage=on_stage)
        ann.switch("solve")
        res = session.run(mesh)
        ann.switch(None)
        for st, (v, t) in zip(steps, meshes):
            st["n_tets"], st["n_verts"] = len(t), len(v)
        self.cycles.append({"steps": steps, "caps": caps, "meshes": meshes,
                            "mesh": res.mesh, "u": res.u})

    def window(self, seconds: float, traced: bool) -> dict:
        self.ann = harness.Annotations(traced)
        t_start = time.perf_counter()
        while True:
            self._cycle()
            elapsed = time.perf_counter() - t_start
            n = len(self.cycles)
            if traced and n >= self.mix["trace_cycles"]:
                break
            if not traced and elapsed + elapsed / n > seconds:
                break
        t_end = time.perf_counter()
        self.checked = int(self.rng_check.integers(len(self.cycles)))
        steps = [s for c in self.cycles for s in c["steps"]]
        return {"t_start": t_start, "t_end": t_end, "attempted": len(steps),
                "e2e": {"adapt_step_s": (t_end - t_start) / len(steps)},
                "steps": steps, "parts": len(self.devices)}

    def check(self, lowp: bool = False) -> dict:
        """Numbers compared after the window, for the cycle drawn from the
        seed, every step of it.  ``lowp``: the control's answers in place of
        the program's (its solve, estimate and marking)."""
        cyc = self.cycles[self.checked]
        prob, a = self.cfg["problem"], self.amplitude
        c = prob["c"]
        f = lambda x: self.problem.source(x, a, c)
        out = {"u_gap": 0.0, "eta_gap": 0.0, "mark_gap": 0.0}
        v_ref, t_ref = kuhn_cylinder(*self.mesh_args)
        faults = int(not (np.array_equal(t_ref, self.t0)
                          and np.array_equal(v_ref, self.v0)))
        for s, cap in enumerate(cyc["caps"]):
            v, t = cyc["meshes"][s]
            g = self.problem.exact(v, a)
            u_ref, _ = ref_fem.solve(v, t, f, g, c, tol=prob["tol"])
            eta_ref = ref_fem.zz_estimate(v, t, u_ref)
            if lowp:
                u, _ = ref_fem.solve(v, t, f, g, c, tol=prob["tol"],
                                     lowp=True)
                eta = ref_fem.zz_estimate(v, t, u, lowp=True)
                marked = ref_fem.doerfler(eta, prob["theta"])
            else:
                u = np.asarray(cap["u"], np.float64)
                eta = np.asarray(cap["eta"], np.float64)
                marked = np.asarray(cap["marked"], bool)
            out["u_gap"] = max(out["u_gap"], float(
                np.abs(u - u_ref).max() / np.abs(u_ref).max()))
            out["eta_gap"] = max(out["eta_gap"], float(
                np.abs(eta - eta_ref).max() / eta_ref.max()))
            e2 = eta_ref ** 2
            want = ref_fem.doerfler(eta_ref, prob["theta"])
            out["mark_gap"] = max(out["mark_gap"], float(
                abs(e2[marked].sum() - e2[want].sum()) / e2.sum()))
            if not lowp and s + 1 < len(cyc["meshes"]):
                faults += ref_fem.refinement_faults(
                    t, marked, cyc["meshes"][s + 1][1])
        if "part_gap" in self.cfg["limits"]:
            out.update(self._partition_gaps(cyc, lowp))
        if lowp:
            return out
        out["refine_faults"] = float(faults)
        p = len(self.devices)
        parts = np.asarray(cyc["mesh"].leaf_payload["parts"])
        out["parts_faults"] = float(
            len(parts) != cyc["mesh"].n_tets or parts.min() < 0
            or parts.max() >= p or len(np.unique(parts)) < min(p, len(parts)))
        u_j = np.asarray(cyc["u"], np.float64)
        out["cycle_gap"] = max(
            float(np.abs(np.asarray(k["u"], np.float64) - u_j).max()
                  / np.abs(u_j).max()) for k in self.cycles)
        return out

    def _partition_gaps(self, cyc: dict, lowp: bool) -> dict:
        """The parts each solve ran on against the reference's partition
        of that step's mesh (``lowp``: the reference's partition of the
        domain distorted by the per-axis box map, in the program's
        place)."""
        p = len(self.devices)
        trigger = self.cfg["balance"]["imbalance_trigger"]
        want = ref_balance.partitions(cyc["meshes"], p, trigger)
        got = (ref_balance.partitions(cyc["meshes"], p, trigger,
                                      uniform=False) if lowp
               else [cap["parts"] for cap in cyc["caps"]])
        return {"part_gap": max(ref_balance.part_gap(g, w, p)
                                for g, w in zip(got, want)),
                "imbalance_gap": max(ref_balance.imbalance(g, p)
                                     for g in got)}
