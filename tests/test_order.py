"""Locality order of owned packings (``fem.order``) and the windowed
matvec kernel it feeds: the order only relabels, the windows hold every
tile, and the owned PCG in that order matches the solve on the twin."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fem import build_elements, build_halo_plan, cylinder_mesh, refine
from repro.fem.assemble import load_vector
from repro.fem.order import order_packing
from repro.fem.parallel import (device_mesh, make_owned_operators,
                                shard_elements, sharded_solve_dirichlet)
from repro.kernels.fem_matvec import LANES, fem_matvec_windows, lane_pad

pytestmark = pytest.mark.skipif(jax.device_count() < 4,
                                reason="needs 4 placeholder devices")


def _packing(p, n_axial=60):
    """An owned packing of a refined long cylinder cut into ``p`` axial
    slabs, the element rows in the packer's order.  Refinement appends
    its vertices and elements, so that order loses the mesh's locality."""
    m = cylinder_mesh(n_axial, 3, length=4.0, radius=0.5)
    refine(m, np.random.default_rng(n_axial).random(m.n_tets) < 0.2)
    el = build_elements(m.verts, m.tets)
    x = m.barycenters()[:, 0]
    parts = np.argsort(np.argsort(x, kind="stable")) * p // m.n_tets
    plan = build_halo_plan(m.tets, parts, m.n_verts, p)
    return m, el, shard_elements(el, parts, p, halo=plan), device_mesh(p)


def _global_elements(tets, lv, vol):
    """Each real element of a part as (sorted global ids, volume)."""
    real = vol > 0
    ids = np.sort(lv[tets[real]], axis=1)
    return sorted(zip(map(tuple, ids), vol[real].tolist()))


@pytest.mark.parametrize("p", [1, 4])
def test_order_relabels_slots_and_permutes_elements(p):
    _, _, sel, jm = _packing(p)
    ordered = order_packing(sel, jm)
    old, new = sel.halo, ordered.halo
    assert new.V % LANES == 0 and new.V >= old.V
    assert ordered.n_interface == sel.n_interface
    S = sel.n_interface
    shared = old.shared_vertex_mask()
    for s in range(p):
        n = old.n_local[s]
        lv_o = np.asarray(old.local_verts[s])
        lv_n = np.asarray(new.local_verts[s])
        # slots: a permutation of the part's vertices, padding after them
        assert sorted(lv_n[:n]) == sorted(lv_o[:n])
        assert (lv_n[n:] == old.n_verts).all()
        own_o = set(lv_o[np.asarray(old.owned_mask[s])])
        assert set(lv_n[np.asarray(new.owned_mask[s])]) == own_o
        g2l = np.asarray(new.global_to_local[s])
        assert (g2l[lv_n[:n]] == np.arange(n)).all()
        # elements: the same ones with the same payload, in a new order
        t_o, t_n = np.asarray(sel.tets[s]), np.asarray(ordered.tets[s])
        v_o, v_n = np.asarray(sel.vol[s]), np.asarray(ordered.vol[s])
        assert _global_elements(t_n, np.append(lv_n, -1), v_n) == \
            _global_elements(t_o, np.append(lv_o, -1), v_o)
        g_o = {tuple(lv_o[t]): g for t, g, v in
               zip(t_o, np.asarray(sel.grads[s]), v_o) if v > 0}
        for t, g, v in zip(t_n, np.asarray(ordered.grads[s]), v_n):
            if v > 0:
                assert np.array_equal(g_o[tuple(lv_n[t])], g)
        # interface elements stay ahead of interior ones
        iface = shared[lv_n[np.minimum(t_n, n - 1)]].any(axis=1) & (v_n > 0)
        assert (np.flatnonzero(iface) < S).all()
        # inside each segment, elements ascend by their smallest slot
        for a, b in ((0, S), (S, len(t_n))):
            key = np.where(v_n[a:b] > 0, t_n[a:b].min(axis=1), new.V)
            assert (np.diff(key) >= 0).all()
    # halo legs: each shipped slot pair still names one global vertex
    send, recv = np.asarray(new.send_idx), np.asarray(new.recv_idx)
    lv = np.asarray(new.local_verts)
    for s in range(p):
        for d in range(p):
            live = send[s, d] < new.V
            assert (recv[d, s][live] < new.V).all()
            assert (lv[s, send[s, d][live]] == lv[d, recv[d, s][live]]).all()


@pytest.mark.parametrize("p", [1, 4])
def test_ordered_windows_hold_every_tile(p):
    """After the order every 128-element tile's corners lie inside its
    lane-aligned window, and the window is a small share of the part."""
    _, _, sel, jm = _packing(p, n_axial=200)
    ordered = order_packing(sel, jm)
    V, S, block = ordered.halo.V, ordered.n_interface, 128
    tets = np.asarray(ordered.tets)
    for a, b in ((0, S), (S, tets.shape[1])):
        if a == b:
            continue
        starts, window, span = fem_matvec_windows(tets[:, a:b], V, block)
        assert window % LANES == 0 and 0 < span <= window
        for s in range(p):
            seg = tets[s, a:b]
            seg = np.concatenate([seg, np.full((-len(seg) % block, 4), V)])
            for k, tile in enumerate(seg.reshape(-1, 4 * block)):
                real = tile[tile < V]
                lo = LANES * starts[s, k]
                assert ((real >= lo) & (real < lo + window)).all()
    _, window, _ = fem_matvec_windows(tets[:, S:], V, block)
    assert window <= lane_pad(V) // 2
    # in the packer's order the same tiles spread over the whole part
    _, loose, _ = fem_matvec_windows(np.asarray(sel.tets)[:, S:],
                                     sel.halo.V, block)
    assert loose > 2 * window


@pytest.mark.parametrize("p", [1, 4])
def test_owned_pcg_in_locality_order_matches_twin(p):
    """The owned PCG on the windowed kernel (interpreted) in locality
    order against the same solve on the XLA twin in the packer's order:
    same solution to the solver's tolerance, iterations within one."""
    from repro.fem.problems import get_problem
    m, el, sel, jm = _packing(p)
    prob = get_problem("helmholtz").make()
    verts = jnp.asarray(m.verts)
    free = np.ones(m.n_verts)
    free[m.boundary_vertices()] = 0.0
    free = jnp.asarray(free)
    rhs = load_vector(el, verts, prob.f)
    g = prob.exact(verts)
    ops = make_owned_operators(sel, jm, prob.c, use_pallas=True,
                               interpret=True)
    assert ops.halo is not sel.halo and ops.halo.V % LANES == 0
    got = sharded_solve_dirichlet(sel, jm, rhs, g, free, prob.c, tol=1e-8,
                                  operators=ops)
    twin = sharded_solve_dirichlet(sel, jm, rhs, g, free, prob.c, tol=1e-8,
                                   use_pallas=True)
    assert float(jnp.max(jnp.abs(got.x - twin.x))) < 1e-5
    assert abs(int(got.iters) - int(twin.iters)) <= 1
