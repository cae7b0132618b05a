"""Owned-vertex halo exchange: plan properties + layout parity.

The property tests pin down the ownership/halo invariants the plan must
satisfy for the reduction to be exact (every cut vertex has exactly one
owner and appears in every toucher's halo, mirrored slot-for-slot on
both sides of each part pair).  The parity tests then check the whole
stack -- matvec, diagonal, PCG solve, adaptive session -- against the
replicated-psum oracle on randomly refined meshes at p in {2, 4, 8}.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fem import build_elements, refine, stiffness_matvec, \
    uniform_refine, unit_cube_mesh
from repro.fem.halo import build_halo_plan, halo_reduce
from repro.fem.solve import owned_vdot, solve_dirichlet
from repro.fem.assemble import load_vector, operator_diagonal

pytestmark = pytest.mark.skipif(jax.device_count() < 8,
                                reason="needs 8 placeholder devices")


def _random_refined_mesh(seed, levels=2, frac=0.3):
    rng = np.random.default_rng(seed)
    m = unit_cube_mesh(2)
    for _ in range(levels):
        refine(m, rng.random(m.n_tets) < frac)
    return m


def _touchers(tets, parts, n_verts, p):
    """set of touching parts per vertex (host oracle)."""
    touch = [set() for _ in range(n_verts)]
    for t, pt in zip(np.asarray(tets), np.asarray(parts)):
        for v in t:
            touch[v].add(int(pt))
    return touch


# ---------------------------------------------------------------------------
# Plan properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 4, 8])
def test_halo_plan_ownership_properties(p):
    m = _random_refined_mesh(p)
    rng = np.random.default_rng(100 + p)
    parts = rng.integers(0, p, m.n_tets)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    touch = _touchers(m.tets, parts, m.n_verts, p)
    owner = np.asarray(plan.owner)
    lv = np.asarray(plan.local_verts)
    om = np.asarray(plan.owned_mask)
    g2l = np.asarray(plan.global_to_local)
    send = np.asarray(plan.send_idx)
    V = plan.V

    n_ghost = 0
    for v in range(m.n_verts):
        T = touch[v]
        if not T:
            assert owner[v] == p                     # untouched: sentinel
            assert not (lv == v).any()
            continue
        assert owner[v] in T
        # exactly one owner slot across all parts
        slots = [(s, g2l[s, v]) for s in range(p) if g2l[s, v] < V]
        assert sorted(s for s, _ in slots) == sorted(T)   # local iff toucher
        owned_at = [s for s, l in slots if om[s, l]]
        assert owned_at == [owner[v]]
        for s, l in slots:
            assert lv[s, l] == v
        # every non-owner toucher ships v to the owner exactly once
        for s in T - {owner[v]}:
            row = send[s, owner[v]]
            assert (row == g2l[s, v]).sum() == 1
            n_ghost += 1
    assert n_ghost == plan.n_ghost_total


@pytest.mark.parametrize("p", [2, 4, 8])
def test_halo_plan_send_recv_mirror(p):
    m = _random_refined_mesh(30 + p)
    rng = np.random.default_rng(200 + p)
    parts = rng.integers(0, p, m.n_tets)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    lv = np.asarray(plan.local_verts)
    send = np.asarray(plan.send_idx)
    recv = np.asarray(plan.recv_idx)
    V = plan.V
    pad_g = plan.n_verts
    for s in range(p):
        for d in range(p):
            sv = np.where(send[s, d] < V, lv[s, np.minimum(send[s, d], V - 1)],
                          pad_g)
            rv = np.where(recv[d, s] < V, lv[d, np.minimum(recv[d, s], V - 1)],
                          pad_g)
            # slot-for-slot the same global vertices on both ends
            assert np.array_equal(sv, rv), (s, d)


def test_halo_plan_handles_empty_parts():
    m = _random_refined_mesh(7, levels=1)
    p = 8
    parts = np.zeros(m.n_tets, np.int64)       # everything on part 0
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    assert plan.n_ghost_total == 0
    assert plan.halo_bytes() == 0
    assert plan.n_owned[0] == len(np.unique(m.tets))
    assert all(c == 0 for c in plan.n_local[1:])


def test_to_local_from_local_roundtrip():
    m = _random_refined_mesh(11)
    p = 4
    rng = np.random.default_rng(3)
    parts = rng.integers(0, p, m.n_tets)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    u = jnp.asarray(rng.random(m.n_verts).astype(np.float32))
    back = plan.from_local(plan.to_local(u))
    active = np.zeros(m.n_verts, bool)
    active[np.unique(m.tets)] = True
    np.testing.assert_allclose(np.asarray(back)[active],
                               np.asarray(u)[active], rtol=0, atol=0)
    assert np.all(np.asarray(back)[~active] == 0.0)


# ---------------------------------------------------------------------------
# Operator / solver parity vs the replicated-psum oracle
# ---------------------------------------------------------------------------

def _partition(m, p):
    from repro.core import Balancer, BalanceSpec
    bal = Balancer.from_spec(BalanceSpec(p=p, method="hsfc"))
    return np.asarray(bal.balance(jnp.ones(m.n_tets),
                                  coords=jnp.asarray(m.barycenters())).parts)


@pytest.mark.parametrize("p", [2, 4, 8])
def test_owned_matvec_parity(p):
    from repro.fem.parallel import (device_mesh, make_sharded_matvec,
                                    shard_elements, sharded_diagonal)
    m = _random_refined_mesh(40 + p)
    el = build_elements(m.verts, m.tets)
    parts = _partition(m, p)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    jmesh = device_mesh(p)
    sel = shard_elements(el, parts, p, halo=plan)
    assert sel.layout == "owned"
    mv, _ = make_sharded_matvec(sel, jmesh, c=1.0)
    u = jnp.asarray(
        np.random.default_rng(p).random(m.n_verts).astype(np.float32))
    ref = stiffness_matvec(el, u, c=1.0)
    out = mv(plan.to_local(u))
    # result correct after reassembly AND ghost-consistent slot-wise
    assert float(jnp.max(jnp.abs(plan.from_local(out) - ref))) < 1e-4
    assert float(jnp.max(jnp.abs(out - plan.to_local(ref)))) < 1e-4
    dref = operator_diagonal(el, 1.0)
    dl = sharded_diagonal(sel, jmesh, 1.0)
    assert float(jnp.max(jnp.abs(plan.from_local(dl) - dref))) < 1e-4


def test_owned_matvec_device_pack_parity():
    from repro.fem.parallel import (device_mesh, make_sharded_matvec,
                                    shard_elements, shard_elements_on_device)
    p = 8
    m = _random_refined_mesh(5)
    el = build_elements(m.verts, m.tets)
    parts = _partition(m, p)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    jmesh = device_mesh(p)
    u = jnp.asarray(
        np.random.default_rng(1).random(m.n_verts).astype(np.float32))
    ul = plan.to_local(u)
    outs = []
    for sel in (shard_elements(el, parts, p, halo=plan),
                shard_elements_on_device(el, jnp.asarray(parts), p, jmesh,
                                         halo=plan)):
        mv, _ = make_sharded_matvec(sel, jmesh, c=1.0)
        outs.append(mv(ul))
    # same operator regardless of element arrival order within a part
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) < 1e-4


def test_owned_matvec_hlo_has_no_global_psum():
    """The owned matvec must communicate only via neighbor collectives --
    no vertex-sized psum anywhere in its jaxpr (the replicated oracle has
    exactly that psum)."""
    from repro.fem.parallel import (device_mesh, make_sharded_matvec,
                                    shard_elements)
    p = 4
    m = _random_refined_mesh(9, levels=1)
    el = build_elements(m.verts, m.tets)
    parts = _partition(m, p)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    jmesh = device_mesh(p)
    mv_o, _ = make_sharded_matvec(shard_elements(el, parts, p, halo=plan),
                                  jmesh, c=1.0)
    u = jnp.zeros(m.n_verts, jnp.float32)
    owned_ir = str(jax.make_jaxpr(mv_o)(plan.to_local(u)))
    assert "psum" not in owned_ir
    assert "all_to_all" in owned_ir
    mv_r, _ = make_sharded_matvec(shard_elements(el, parts, p), jmesh, c=1.0)
    assert "psum" in str(jax.make_jaxpr(mv_r)(u))


@pytest.mark.parametrize("p", [2, 4, 8])
def test_owned_pcg_solution_parity(p):
    from repro.fem.parallel import (device_mesh, shard_elements,
                                    sharded_solve_dirichlet)
    m = _random_refined_mesh(60 + p)
    el = build_elements(m.verts, m.tets)
    parts = _partition(m, p)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    jmesh = device_mesh(p)
    sel = shard_elements(el, parts, p, halo=plan)

    from repro.fem.problems import get_problem
    prob = get_problem("helmholtz").make()
    verts = jnp.asarray(m.verts)
    free = np.ones(m.n_verts)
    free[m.boundary_vertices()] = 0.0
    free = jnp.asarray(free)
    rhs = load_vector(el, verts, prob.f)
    g = prob.exact(verts)
    ref = solve_dirichlet(el, rhs, g, free, prob.c, tol=1e-8)
    got = sharded_solve_dirichlet(sel, jmesh, rhs, g, free, prob.c, tol=1e-8)
    assert float(jnp.max(jnp.abs(got.x - ref.x))) < 1e-5
    assert int(got.iters) <= int(ref.iters) + 10


def test_owned_vdot_counts_shared_dofs_once():
    m = _random_refined_mesh(13, levels=1)
    p = 4
    parts = _partition(m, p)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.random(m.n_verts).astype(np.float64))
    b = jnp.asarray(rng.random(m.n_verts).astype(np.float64))
    got = float(owned_vdot(plan.owned_mask)(plan.to_local(a),
                                            plan.to_local(b)))
    active = np.zeros(m.n_verts, bool)
    active[np.unique(m.tets)] = True
    want = float(np.sum(np.asarray(a)[active] * np.asarray(b)[active]))
    assert abs(got - want) < 1e-4 * max(1.0, abs(want))


def test_adaptive_session_owned_matches_replicated():
    """Both registered problems, sharded backend: the owned-vertex loop
    reproduces the replicated loop's mesh and solution (generic box
    geometry -- no eta ties, so marking is layout-independent)."""
    from repro.core import BalanceSpec
    from repro.fem import AdaptSpec, AdaptiveSession, kuhn_box_mesh

    def mk():
        return kuhn_box_mesh(2, 2, 2, lengths=(1.0, 0.83, 0.71))

    for prob, kw in [("helmholtz", dict(max_steps=2, max_tets=1500)),
                     ("parabolic", dict(trigger="always", dt=0.01, n_steps=2,
                                        max_tets=1500))]:
        runs = {}
        for layout in ("replicated", "owned"):
            spec = AdaptSpec.for_problem(
                prob, backend="sharded", vertex_layout=layout, tol=1e-8,
                balance=BalanceSpec(p=8, method="hsfc"), **kw)
            runs[layout] = AdaptiveSession(spec).run(mk())
        a, b = runs["replicated"], runs["owned"]
        assert np.array_equal(a.mesh.tets, b.mesh.tets), prob
        gap = float(np.max(np.abs(np.asarray(a.u) - np.asarray(b.u))))
        assert gap < 2e-5, (prob, gap)
        assert b.halo is not None
        assert b.sharded.layout == "owned"
        last = b.stats[-1]
        assert last.cut is not None and last.cut > 0
        assert 0 < last.comm_halo_bytes < last.comm_psum_bytes


# ---------------------------------------------------------------------------
# Interface-split packing + overlapped matvec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 4, 8])
def test_split_packing_classifies_interface_first(p):
    """Owned packings order each part's row interface-first: every element
    touching a shared vertex sits before every element that doesn't, and
    the jit-static split point covers the per-part interface counts."""
    from repro.fem.parallel import (device_mesh, shard_elements,
                                    shard_elements_on_device)
    m = _random_refined_mesh(70 + p)
    el = build_elements(m.verts, m.tets)
    parts = _partition(m, p)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    shared = plan.shared_vertex_mask()
    lv = np.asarray(plan.local_verts)
    packs = [shard_elements(el, parts, p, halo=plan),
             shard_elements_on_device(el, jnp.asarray(parts), p,
                                      device_mesh(p), halo=plan)]
    for sel in packs:
        S = sel.n_interface
        assert S is not None
        tets = np.asarray(sel.tets)
        vol = np.asarray(sel.vol)
        def row_iface(r):
            # clamp twice: pad elements -> slot V, pad slots -> vertex
            # n_verts; both land on & valid below
            gv = lv[r, np.minimum(tets[r], plan.V - 1)]
            return (shared[np.minimum(gv, plan.n_verts - 1)].any(axis=1)
                    & (vol[r] > 0))

        for r in range(p):
            valid = vol[r] > 0
            iface = row_iface(r)
            flags = iface.astype(int) * 2 + valid.astype(int)
            # interface (3) strictly before interior (1) before padding (0)
            assert (np.diff(flags) <= 0).all(), r
            assert iface.sum() <= S
        assert max(int(row_iface(r).sum()) for r in range(p)) == S


@pytest.mark.parametrize("p", [2, 4, 8])
def test_split_matvec_matches_unsplit(p):
    """The overlapped (interface-first) matvec equals the serial
    apply-everything-then-exchange oracle on the same packing; exact up
    to f32 summation order."""
    from repro.fem.parallel import (device_mesh, make_sharded_matvec,
                                    shard_elements, sharded_diagonal)
    m = _random_refined_mesh(80 + p)
    el = build_elements(m.verts, m.tets)
    parts = _partition(m, p)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    jmesh = device_mesh(p)
    sel = shard_elements(el, parts, p, halo=plan)
    u = jnp.asarray(
        np.random.default_rng(p).random(m.n_verts).astype(np.float32))
    ul = plan.to_local(u)
    mv_split, _ = make_sharded_matvec(sel, jmesh, c=1.0, overlap=True)
    mv_serial, _ = make_sharded_matvec(sel, jmesh, c=1.0, overlap=False)
    gap = float(jnp.max(jnp.abs(mv_split(ul) - mv_serial(ul))))
    assert gap < 1e-5
    # diagonal is split-agnostic (same packing, full-row reduction)
    d = sharded_diagonal(sel, jmesh, 1.0)
    dref = operator_diagonal(el, 1.0)
    assert float(jnp.max(jnp.abs(plan.from_local(d) - dref))) < 1e-4


def test_split_matvec_jaxpr_orders_exchange_before_interior():
    """The whole point of the split: in the overlapped jaxpr the two
    all_to_all legs are traced BEFORE the interior element flops (so XLA
    can hide the exchange), i.e. element dot_generals appear after the
    last all_to_all.  The unsplit oracle finishes every element before
    the first leg -- nothing left to overlap."""
    from repro.fem.parallel import (device_mesh, make_sharded_matvec,
                                    shard_elements)
    p = 4
    m = _random_refined_mesh(17)
    el = build_elements(m.verts, m.tets)
    parts = _partition(m, p)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    jmesh = device_mesh(p)
    sel = shard_elements(el, parts, p, halo=plan)
    u = plan.to_local(jnp.zeros(m.n_verts, jnp.float32))
    mv_split, _ = make_sharded_matvec(sel, jmesh, c=1.0, overlap=True)
    ir = str(jax.make_jaxpr(mv_split)(u))
    assert "all_to_all" in ir
    assert "dot_general" in ir[ir.rindex("all_to_all"):]
    mv_serial, _ = make_sharded_matvec(sel, jmesh, c=1.0, overlap=False)
    ir = str(jax.make_jaxpr(mv_serial)(u))
    assert "dot_general" not in ir[ir.index("all_to_all"):]


def test_split_matvec_handles_no_interface():
    """Everything on one part: no shared vertices, split point 0, the
    interface pass is empty -- the overlapped matvec still matches the
    dense oracle (and the other 7 parts are fully empty)."""
    from repro.fem.parallel import (device_mesh, make_sharded_matvec,
                                    shard_elements)
    p = 8
    m = _random_refined_mesh(23, levels=1)
    el = build_elements(m.verts, m.tets)
    parts = np.zeros(m.n_tets, np.int64)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    assert plan.n_ghost_total == 0
    sel = shard_elements(el, parts, p, halo=plan)
    assert sel.n_interface == 0
    jmesh = device_mesh(p)
    mv, _ = make_sharded_matvec(sel, jmesh, c=1.0)      # overlap defaults on
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.random(m.n_verts).astype(np.float32))
    out = mv(plan.to_local(u))
    ref = stiffness_matvec(el, u, c=1.0)
    assert float(jnp.max(jnp.abs(plan.from_local(out) - ref))) < 1e-4


@pytest.mark.parametrize("p", [2, 8])
def test_owned_pcg_pallas_kernel_parity(p):
    """Full PCG through the fused element kernel (its XLA twin off-TPU):
    same solution as the geometry-oracle solve."""
    from repro.fem.parallel import (device_mesh, shard_elements,
                                    sharded_solve_dirichlet)
    m = _random_refined_mesh(90 + p)
    el = build_elements(m.verts, m.tets)
    parts = _partition(m, p)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    jmesh = device_mesh(p)
    sel = shard_elements(el, parts, p, halo=plan)

    from repro.fem.problems import get_problem
    prob = get_problem("helmholtz").make()
    verts = jnp.asarray(m.verts)
    free = np.ones(m.n_verts)
    free[m.boundary_vertices()] = 0.0
    free = jnp.asarray(free)
    rhs = load_vector(el, verts, prob.f)
    g = prob.exact(verts)
    ref = sharded_solve_dirichlet(sel, jmesh, rhs, g, free, prob.c,
                                  tol=1e-8, use_pallas=False)
    serial = sharded_solve_dirichlet(sel, jmesh, rhs, g, free, prob.c,
                                     tol=1e-8, overlap=False,
                                     use_pallas=False)
    got = sharded_solve_dirichlet(sel, jmesh, rhs, g, free, prob.c,
                                  tol=1e-8, use_pallas=True)
    assert float(jnp.max(jnp.abs(serial.x - ref.x))) < 1e-5
    assert float(jnp.max(jnp.abs(got.x - ref.x))) < 1e-5
    assert int(got.iters) <= int(ref.iters) + 10


@pytest.mark.parametrize("path", ["overlap", "serial", "kernel",
                                  "replicated"])
def test_matvec_scope_names_the_matvec_in_jit_solve(path):
    """Every program that applies the operator carries the matvec's
    named scope in its op metadata, whichever path built the matvec: the
    owned PCG's ``solve`` program (split, serial, kernel twin) and the
    replicated twin."""
    from repro.fem.parallel import (MATVEC_SCOPE, device_mesh,
                                    make_sharded_matvec, shard_elements,
                                    sharded_solve_dirichlet)
    p = 2
    m = _random_refined_mesh(41, levels=1)
    el = build_elements(m.verts, m.tets)
    parts = _partition(m, p)
    jmesh = device_mesh(p)
    x = jnp.ones(m.n_verts, jnp.float32)
    if path == "replicated":
        sel = shard_elements(el, parts, p)
        mv, _ = make_sharded_matvec(sel, jmesh, c=1.0)
        text = jax.jit(mv).lower(x).as_text(debug_info=True)
    else:
        plan = build_halo_plan(m.tets, parts, m.n_verts, p)
        sel = shard_elements(el, parts, p, halo=plan)

        def solve(rhs, g, free):
            return sharded_solve_dirichlet(
                sel, jmesh, rhs, g, free, 1.0, overlap=path != "serial",
                use_pallas=path == "kernel")
        text = jax.jit(solve).lower(x, x, x).as_text(debug_info=True)
        assert "@solve" in text
    # a scope location, not the kernel module's file name
    assert f'loc("{MATVEC_SCOPE}/' in text


def test_halo_bytes_follow_solve_itemsize():
    """The wire model is dtype-aware: doubling the itemsize doubles both
    byte figures (the adaptive session passes the actual solve dtype's
    itemsize instead of assuming f32)."""
    m = _random_refined_mesh(37, levels=1)
    p = 4
    parts = _partition(m, p)
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    assert plan.halo_bytes(itemsize=8) == 2 * plan.halo_bytes()
    assert plan.psum_bytes(itemsize=8) == 2 * plan.psum_bytes()


def test_halo_bytes_scale_with_cut_not_mesh_size():
    """Refining the mesh under a fixed part count grows psum bytes like
    n_verts but halo bytes like the cut surface (~ volume^(2/3)): at 7x
    the vertices the halo costs ~2.5x, the psum 7x (measured 0.34 ->
    0.12 halo/psum ratio over two uniform refinements at p=8)."""
    p = 8
    sizes = []
    for levels in (0, 4):
        m = unit_cube_mesh(2)
        uniform_refine(m, levels)
        parts = _partition(m, p)
        plan = build_halo_plan(m.tets, parts, m.n_verts, p)
        sizes.append((m.n_verts, plan.psum_bytes(), plan.halo_bytes()))
    (nv0, ps0, hb0), (nv1, ps1, hb1) = sizes
    assert nv1 > 5 * nv0
    assert ps1 / ps0 == pytest.approx(nv1 / nv0)
    # halo grows clearly sublinearly in the vertex count
    assert hb1 / hb0 < 0.6 * (ps1 / ps0)
