"""Distributed matrix-free FEM operator via shard_map.

This is the compute model of the paper (section 1): each process owns the
sub-mesh the balancer assigned to it and computes element-local work; the
global vertex reduction is the inter-process communication.

Two element-distribution paths:

* ``shard_elements``           host loop packing (p, C, ...) arrays --
                               the control-plane path for tests/setup.
* ``shard_elements_on_device`` the production path: element payloads
                               move between shards with the migration
                               executor's single ``all_to_all`` (no host
                               loop); ``reshard_elements`` composes it
                               with the sharded ``Balancer`` pipeline so
                               the adaptive loop re-partitions AND
                               re-shards after every refinement step on
                               device.

JAX mapping: element arrays are laid out as (p, C, ...) -- one row per
part, padded to the capacity C = max part size (capacity comes from the
same prefix-sum machinery as the partition itself).  The matvec inside
``shard_map`` does the local gather->apply->scatter and the shared-vertex
reduction.  The partition quality (surface index) controls exactly how
much of that reduction is inter-process -- the quantity the paper's
geometric methods trade against partition speed.

Two vertex layouts (``vertex_layout`` on the operators):

* ``"replicated"``  the vertex vector is (n_verts,) on every device and
                    the reduction is one global ``psum`` -- O(n_verts)
                    wire traffic per matvec regardless of partition
                    quality.  Kept as the parity oracle.
* ``"owned"``       vertices are sharded by owner part (``fem.halo``):
                    vectors are (p, V) with locally renumbered
                    connectivity, and the reduction is
                    ``halo.halo_reduce`` -- two neighbor ``all_to_all``
                    legs whose wire volume is proportional to the
                    partition's cut (the surface index), not the mesh
                    size.  This is the production path (see ROADMAP's
                    "Owned-vertex FEM layer" migration guide; the
                    replicated psum used to be called out here as the
                    known production gap).

The owned hot path layers two optimizations on top (README "FEM hot
path"): packings are interface-first (``ShardedElements.n_interface``)
so the matvec can hand the interface partials to ``halo_reduce`` before
the interior elements run -- XLA hides the exchange behind the interior
FLOPs (``overlap=``) -- and the per-element work can dispatch to the
fused ``kernels.fem_matvec`` element kernel (``use_pallas=`` /
``interpret=``, threaded from ``BalanceSpec.use_pallas`` by the
adaptive session), whose per-tile vertex windows stay narrow because
``make_owned_operators`` first puts the packing in locality order
(``fem.order``).  Every matvec body runs under
``jax.named_scope(MATVEC_SCOPE)``, so each program that applies the
operator (the PCG loop of ``jit_solve`` among them) names the matvec's
operations alike in its op metadata, whichever path built it.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh as JMesh
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry
from ..distributed.sharding import shard_map
from ..kernels.fem_matvec import (fem_element_matrices, fem_matvec_jnp,
                                  fem_matvec_tiles, fem_matvec_window,
                                  fem_matvec_windows, gather_corners)
from ..kernels.ops import fem_matvec_path, on_tpu
from .assemble import _HIGHEST, _MASS, P1Elements
from .halo import HaloPlan, build_halo_plan, halo_reduce
from .order import order_packing
from .solve import CGResult, owned_vdot, pcg

AXIS = "fem"
# named scope of the matvec's operations in every program that applies it
MATVEC_SCOPE = "fem_matvec"

VERTEX_LAYOUTS = ("replicated", "owned")


def device_mesh(p: int, *, devices=None) -> JMesh:
    """1-D jax device mesh over the first ``p`` devices on axis ``AXIS``.

    The single construction point for the FEM layer's device topology
    (the adaptive session, ``reshard_elements`` and the examples all go
    through here)."""
    devs = jax.devices() if devices is None else list(devices)
    if len(devs) < p:
        raise ValueError(f"need {p} devices for the FEM mesh, have "
                         f"{len(devs)} (set "
                         "--xla_force_host_platform_device_count)")
    return JMesh(np.array(devs[:p]), (AXIS,))


class ShardedElements(NamedTuple):
    """(p, C, ...) per-part element packing.

    ``layout="replicated"``: ``tets`` holds global vertex ids (padding 0,
    vol 0 makes padded elements no-ops).  ``layout="owned"``: ``tets``
    holds part-local slot ids into the ``halo`` plan's (p, V) vertex
    layout (padding ``halo.V``, dropped by the local scatter), packed
    *interface-first*: each part's row leads with its elements that touch
    a shared vertex (``HaloPlan.shared_vertex_mask``), and
    ``n_interface`` is the jit-static split point -- the max per-part
    interface count.  Rows of a part with fewer interface elements carry
    interior (or padding) elements in ``[count, n_interface)``; they pass
    through the interface pass harmlessly because they contribute nothing
    to any slot ``halo_reduce`` touches."""
    tets: jax.Array    # (p, C, 4) int32
    grads: jax.Array   # (p, C, 4, 3)
    vol: jax.Array     # (p, C)  (0 on padding -> padded elements are no-ops)
    n_verts: int
    p: int
    halo: Optional[HaloPlan] = None
    layout: str = "replicated"
    # static interface/interior split (owned layout): elements [0, S) of
    # every part feed the halo exchange, [S, C) overlap it.  None on
    # replicated packings (no exchange to overlap).
    n_interface: Optional[int] = None


def _resolve_layout(sel: ShardedElements, vertex_layout: Optional[str]) -> str:
    layout = sel.layout if vertex_layout is None else vertex_layout
    if layout not in VERTEX_LAYOUTS:
        raise ValueError(f"unknown vertex_layout {layout!r}; "
                         f"choose from {VERTEX_LAYOUTS}")
    if layout != sel.layout:
        raise ValueError(
            f"vertex_layout={layout!r} needs elements packed with that "
            f"layout (got layout={sel.layout!r}; pass halo= to the packer)")
    if layout == "owned" and sel.halo is None:
        raise ValueError("owned layout needs a HaloPlan on the packing")
    return layout


def shard_elements(el: P1Elements, parts: np.ndarray, p: int,
                   halo: Optional[HaloPlan] = None) -> ShardedElements:
    """Pack per-part element lists padded to max part size.

    With ``halo`` given, connectivity is renumbered to part-local slots
    (owned layout); padding rows point at slot ``halo.V`` so the local
    scatter drops them.  Owned rows are packed interface-first (elements
    touching a shared vertex lead) with the static split point
    ``n_interface`` carried on the packing, so the owned matvec can hand
    the interface partials to the halo exchange before interior work."""
    parts = np.asarray(parts)
    tets = np.asarray(el.tets)
    grads = np.asarray(el.grads)
    vol = np.asarray(el.vol)
    counts = np.bincount(parts, minlength=p)
    C = int(counts.max())
    pad_vert = 0 if halo is None else halo.V
    st = np.full((p, C, 4), pad_vert, np.int32)
    sg = np.zeros((p, C, 4, 3), grads.dtype)
    sv = np.zeros((p, C), vol.dtype)
    g2l = None if halo is None else np.asarray(halo.global_to_local)
    iface = n_interface = None
    if halo is not None:
        iface = halo.shared_vertex_mask()[tets].any(axis=1)
        n_interface = 0
    for i in range(p):
        idx = np.flatnonzero(parts == i)
        if iface is not None:
            f = iface[idx]
            idx = np.concatenate([idx[f], idx[~f]])    # interface first
            n_interface = max(n_interface, int(f.sum()))
        t = tets[idx]
        st[i, :idx.size] = t if halo is None else g2l[i, t]
        sg[i, :idx.size] = grads[idx]
        sv[i, :idx.size] = vol[idx]
    return ShardedElements(jnp.asarray(st), jnp.asarray(sg), jnp.asarray(sv),
                           el.n_verts, p, halo=halo,
                           layout="replicated" if halo is None else "owned",
                           n_interface=n_interface)


def shard_elements_on_device(el: P1Elements, parts: jax.Array, p: int,
                             mesh: JMesh,
                             halo: Optional[HaloPlan] = None
                             ) -> ShardedElements:
    """Pack per-part element lists with the migration executor.

    Elements start index-sharded (shard r owns global rows [rC, (r+1)C));
    one ``all_to_all`` inside shard_map delivers each element's payload
    (connectivity, gradients, volume) to the shard the partition assigned
    it.  The only host work is sizing the receive capacity from the part
    counts (the same quantity the host packer needs for its array shapes).
    Padding rows keep vol = 0 so they are no-ops in the sharded matvec.

    With ``halo`` given, the halo plan's payload migrates alongside: each
    shard's ``global_to_local`` row rides on the same device mesh and
    renumbers the received connectivity to part-local slots inside the
    same shard_map region (owned layout; padding/invalid rows point at
    slot ``halo.V``).  An interface flag per element (does it touch a
    shared vertex -- classified on the host against the plan, like the
    receive capacity) rides on the same ``all_to_all``; a stable argsort
    on arrival reorders each shard's row interface-first, and the static
    split point ``n_interface`` (max per-part interface count, from the
    same bincount that sizes the capacity) lands on the packing.
    """
    from ..distributed.migrate import migrate_items
    parts_h = np.asarray(parts)
    n = int(parts_h.shape[0])
    C_in = -(-n // p)
    n_pad = p * C_in
    cap = int(np.bincount(parts_h, minlength=p).max())

    def pad(a, dtype=None):
        a = jnp.asarray(a) if dtype is None else jnp.asarray(a, dtype)
        if n_pad == n:
            return a
        return jnp.concatenate(
            [a, jnp.zeros((n_pad - n,) + a.shape[1:], a.dtype)])

    tets = pad(el.tets, jnp.int32)
    grads = pad(el.grads)
    vol = pad(el.vol)
    dest = pad(parts, jnp.int32)
    n_interface = iface = None
    if halo is not None:
        iface_h = halo.shared_vertex_mask()[np.asarray(el.tets)].any(axis=1)
        n_interface = int(np.bincount(parts_h[iface_h], minlength=p).max())
        iface = pad(iface_h.astype(np.int32), jnp.int32)

    def local(tets_l, grads_l, vol_l, dest_l, *extra):
        rank = jax.lax.axis_index(AXIS)
        valid = rank * C_in + jnp.arange(C_in) < n
        payload = {"tets": tets_l, "grads": grads_l, "vol": vol_l}
        if halo is not None:
            payload["iface"] = extra[0]
        mig = migrate_items(payload, dest_l, vol_l, AXIS, p, valid=valid,
                            capacity=cap)
        t, g, v = (mig.payload["tets"], mig.payload["grads"],
                   mig.payload["vol"])
        val = mig.valid
        if halo is None:
            t = jnp.where(val[:, None], t, 0)
        else:
            # interface-first within the shard: stable argsort on
            # (0 = interface, 1 = interior, 2 = padding) keeps arrival
            # order inside each class and pushes padding last
            key = jnp.where(val, jnp.where(mig.payload["iface"] > 0, 0, 1),
                            2)
            order = jnp.argsort(key)
            t, g, v, val = t[order], g[order], v[order], val[order]
            # renumber to part-local slots; invalid/padding -> slot V
            t = gather_corners(extra[1][0],
                               jnp.minimum(t, halo.n_verts - 1))
            t = jnp.where(val[:, None], t, halo.V)
        g = jnp.where(val[:, None, None], g, 0.0)
        v = jnp.where(val, v, 0.0)
        return t, g, v

    n_in = 4 if halo is None else 6
    packed = shard_map(local, mesh=mesh, in_specs=(P(AXIS),) * n_in,
                       out_specs=(P(AXIS),) * 3)

    @jax.jit
    def pack_elements(*args):
        st, sg, sv = packed(*args)
        return st.reshape(p, cap, 4), sg.reshape(p, cap, 4, 3), \
            sv.reshape(p, cap)

    args = (tets, grads, vol, dest)
    if halo is not None:
        args = args + (iface, halo.global_to_local)
    st, sg, sv = pack_elements(*args)
    return ShardedElements(st, sg, sv, el.n_verts, p, halo=halo,
                           layout="replicated" if halo is None else "owned",
                           n_interface=n_interface)


def reshard_elements(el: P1Elements, coords: jax.Array, p: int, *,
                     mesh: Optional[JMesh] = None,
                     old_parts: Optional[jax.Array] = None,
                     balancer=None, spec=None,
                     vertex_layout: str = "replicated"):
    """One full on-device DLB step for the FEM layer: partition + remap
    inside one jitted shard_map region (``Balancer`` with
    ``backend='sharded'``), then element payload migration via
    ``all_to_all``.  Returns (ShardedElements, result).

    ``vertex_layout="owned"`` additionally derives the halo plan from the
    fresh partition (``fem.halo.build_halo_plan``) and packs locally
    renumbered connectivity, so the returned elements drive the
    halo-exchange operators directly.

    Convenience one-call entry for examples/library users.  In a loop,
    pass a persistent ``balancer`` (a ``repro.core.Balancer`` or the
    legacy ``DistributedBalancer``) so its compiled pipelines are reused;
    ``spec`` overrides the default ``BalanceSpec`` when no balancer is
    given.  The adaptive driver, which balances and packs at different
    points of its step, composes the stages itself instead.
    """
    from ..core.spec import Balancer, BalanceSpec
    if vertex_layout not in VERTEX_LAYOUTS:
        raise ValueError(f"unknown vertex_layout {vertex_layout!r}; "
                         f"choose from {VERTEX_LAYOUTS}")
    if balancer is None:
        if spec is None:
            spec = BalanceSpec(p=p, method="hsfc", backend="sharded")
        balancer = Balancer.from_spec(spec)
    if mesh is None:
        mesh = device_mesh(p)
    w = jnp.ones(el.tets.shape[0], jnp.float32)
    res = balancer.balance(w, coords=coords, old_parts=old_parts)
    halo = None
    if vertex_layout == "owned":
        halo = build_halo_plan(np.asarray(el.tets), np.asarray(res.parts),
                               el.n_verts, p)
    sel = shard_elements_on_device(el, res.parts, p, mesh, halo=halo)
    return sel, res


def element_apply(t, g, v, u, nv, c=0.0):
    """Element-local gather -> geometry apply -> scatter (the oracle pass).

    Padded elements have g = 0, v = 0 -> au = 0 there, so clamped gathers
    and dropped/clipped scatter ids never contribute."""
    ue = gather_corners(u, jnp.minimum(t, nv - 1))    # (C, 4); pad -> x0
    flux = jnp.einsum("cid,ci->cd", g, ue, precision=_HIGHEST)
    au = jnp.einsum("cjd,cd->cj", g, flux, precision=_HIGHEST) * v[:, None]
    if c != 0.0:
        au = au + c * jnp.einsum("ij,cj->ci", _MASS, ue,
                                 precision=_HIGHEST) * v[:, None]
    return jax.ops.segment_sum(au.reshape(-1), t.reshape(-1),
                               num_segments=nv)


class MatvecPlan(NamedTuple):
    """How the owned matvec applies its elements, decided on the host
    from the packing's shapes when the operator is built.

    ``path``: ``"window"`` (the windowed Pallas kernel), ``"xla"`` (its
    gather/scatter twin) or ``"oracle"`` (the geometry einsum pass).
    For the kernel, per element segment (the interface and interior
    passes, or one pass without overlap): ``windows`` the static window
    width ``W`` in slots and ``starts`` the (p, tiles) first window rows;
    ``span`` the widest tile's extent of real slots."""
    path: str
    windows: Tuple[int, ...] = ()
    starts: Tuple[np.ndarray, ...] = ()
    span: int = 0


def _segments(sel: ShardedElements, overlap: bool):
    """Element ranges of the matvec's passes: the interface elements and
    the interior ones with overlap, everything in one pass without."""
    C = sel.tets.shape[1]
    S = sel.n_interface
    return ((0, S), (S, C)) if overlap else ((0, C),)


def _resolve_overlap(sel: ShardedElements, overlap: Optional[bool]) -> bool:
    if overlap is None:
        return sel.n_interface is not None
    if overlap and sel.n_interface is None:
        raise ValueError("overlap needs an interface-split packing "
                         "(repack with shard_elements*/reshard_elements, "
                         "which set n_interface for owned layouts)")
    return overlap


def matvec_plan(sel: ShardedElements, *, overlap: bool,
                use_pallas: Optional[bool] = None,
                interpret: bool = False) -> MatvecPlan:
    """The owned matvec's path for this packing.  ``use_pallas``
    (``None``: on a TPU) asks for the kernel; it runs compiled on a TPU
    where every segment's window fits the kernel's VMEM rule
    (``kernels.ops.fem_matvec_path``), interpreted where ``interpret``
    says so, and otherwise the twin runs.  Reads the packing's slot ids
    on the host only where the kernel may run."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return MatvecPlan("oracle")
    if not (interpret or on_tpu()):
        return MatvecPlan("xla")
    tets = np.asarray(sel.tets)
    V = sel.halo.V
    wins = [fem_matvec_windows(tets[:, a:b], V)
            for a, b in _segments(sel, overlap)]
    windows = tuple(w for _, w, _ in wins)
    path = fem_matvec_path(V, window=max(windows), use_pallas=True,
                           interpret=interpret)
    return MatvecPlan(path, windows, tuple(st for st, _, _ in wins),
                      max(sp for _, _, sp in wins))


@partial(jax.jit, static_argnums=(3, 4, 5))
def _kernel_operands(tets, grads, vol, c, n_out, segments):
    """The windowed kernel's streamed operands of each pass
    (``fem_matvec_tiles``), from the packing in one program."""
    kel = fem_element_matrices(grads, vol, c)
    return tuple(fem_matvec_tiles(tets[:, a:b], kel[:, a:b], n_out)
                 for a, b in segments)


def make_sharded_matvec(sel: ShardedElements, mesh: JMesh, c: float = 0.0,
                        vertex_layout: Optional[str] = None, *,
                        overlap: Optional[bool] = None,
                        use_pallas: Optional[bool] = None,
                        interpret: bool = False,
                        plan: Optional[MatvecPlan] = None
                        ) -> Tuple[Callable, tuple]:
    """Returns (matvec, element arrays placed on the mesh).

    ``vertex_layout`` (default: the packing's own layout):

    * ``"replicated"``: matvec maps (nv,) replicated -> (nv,) replicated,
      one global ``psum`` over AXIS.
    * ``"owned"``: matvec maps (p, V) -> (p, V), both sharded ``P(AXIS)``
      in the packing's halo-plan layout; the reduction is
      ``halo_reduce`` (two neighbor ``all_to_all`` legs, no psum).  The
      input must be ghost-consistent (every copy of a shared vertex
      equal -- what ``HaloPlan.to_local`` and the matvec itself
      produce), and the output is ghost-consistent again.

    Owned-layout hot-path knobs:

    ``overlap`` (default: on whenever the packing carries a split point)
      computes the interface elements ``[0, n_interface)`` first and
      hands their partials to the halo exchange *before* the interior
      elements run, so XLA can hide the two ``all_to_all`` legs behind
      the interior FLOPs.  Exact up to float summation order: interior
      elements touch no shared vertex, so
      ``halo_reduce(y_if) + y_int == halo_reduce(y_if + y_int)``.
      ``overlap=False`` forces the serial apply-everything-then-exchange
      oracle (the parity and micro-benchmark baseline).
    ``use_pallas`` / ``interpret`` select the per-element work through
      ``matvec_plan`` (unless ``plan`` gives it): ``None`` auto-selects
      on TPU, ``False`` is the inline einsum oracle, ``True`` the
      windowed kernel (``kernels.fem_matvec``: precomputed 4x4 element
      matrices streamed through one launch per pass) where its windows
      fit, else the fused-XLA twin; the twin off-TPU, or the Pallas
      interpreter when ``interpret=True``.  The kernel's windows follow
      the packing's order: ``make_owned_operators`` orders the packing
      first (``fem.order``).  Kernel and oracle are tolerance-exact, not
      bit-exact (different accumulation order).
    """
    layout = _resolve_layout(sel, vertex_layout)
    spec_el = NamedSharding(mesh, P(AXIS))
    tets = jax.device_put(sel.tets, spec_el)
    grads = jax.device_put(sel.grads, spec_el)
    vol = jax.device_put(sel.vol, spec_el)

    if layout == "replicated":
        nv = sel.n_verts

        def local_apply(tets_l, grads_l, vol_l, u):
            with jax.named_scope(MATVEC_SCOPE):
                # (1, C, ...) block -> squeeze the part dim
                y = element_apply(tets_l[0], grads_l[0], vol_l[0], u, nv, c)
                return jax.lax.psum(y, AXIS)

        shmap = jax.jit(shard_map(
            local_apply, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
            out_specs=P()))

        def matvec(u):
            return shmap(tets, grads, vol, u)

        return matvec, (tets, grads, vol)

    halo = sel.halo
    overlap = _resolve_overlap(sel, overlap)
    segments = _segments(sel, overlap)
    if plan is None:
        plan = matvec_plan(sel, overlap=overlap, use_pallas=use_pallas,
                           interpret=interpret)
    # per-element 4x4 operators: constant across matvecs on a fixed
    # packing, so build once here and stream per call
    if plan.path == "oracle":
        el_args = (tets, grads, vol)
    elif plan.path == "xla":
        kel = fem_element_matrices(sel.grads, sel.vol, c)
        el_args = (tets, grads, vol, jax.device_put(kel, spec_el))
    else:
        # the kernel's tiles and windows per pass, laid out once
        tiles = _kernel_operands(tets, grads, vol, c, halo.V, segments)
        el_args = tuple(jax.device_put(x, spec_el) for i, st in
                        enumerate(plan.starts) for x in tiles[i] + (st,))

    def apply_elements(i, a, u):
        # pass i of the matvec over this part's (1, ...) blocks
        if plan.path == "window":
            ids, ks, starts = (x[0] for x in a[3 * i:3 * i + 3])
            return fem_matvec_window(ids, ks, starts, u, halo.V,
                                     window=plan.windows[i],
                                     interpret=interpret)
        sl = slice(*segments[i])
        t, g, v = a[0][0][sl], a[1][0][sl], a[2][0][sl]
        if plan.path == "xla":
            return fem_matvec_jnp(t, a[3][0][sl], u, halo.V)
        return element_apply(t, g, v, u, halo.V, c)

    def local_apply_owned(*a):
        send, recv, u = a[-3][0], a[-2][0], a[-1][0]
        with jax.named_scope(MATVEC_SCOPE):
            if not overlap:
                return halo_reduce(apply_elements(0, a, u), send, recv,
                                   AXIS)[None]
            # interface pass first: its partials are all the two all_to_all
            # legs consume, so tracing it before the interior pass puts the
            # collectives ahead of the interior FLOPs in program order --
            # XLA overlaps the neighbor exchange with the interior elements.
            y = halo_reduce(apply_elements(0, a, u), send, recv, AXIS)
            return (y + apply_elements(1, a, u))[None]

    send_idx = jax.device_put(halo.send_idx, spec_el)
    recv_idx = jax.device_put(halo.recv_idx, spec_el)
    # pallas_call has no shard_map replication rule; nothing in the owned
    # region is replicated (everything is P(AXIS)), so the check is vacuous.
    # Jitted: called eagerly (the PCG lift), an un-jitted shard_map would
    # dispatch and compile its body one primitive at a time.
    shmap = jax.jit(shard_map(
        local_apply_owned, mesh=mesh,
        in_specs=(P(AXIS),) * (len(el_args) + 3), out_specs=P(AXIS),
        check_vma=plan.path == "oracle"))

    def matvec_owned(u):
        return shmap(*el_args, send_idx, recv_idx, u)

    return matvec_owned, el_args + (send_idx, recv_idx)


def sharded_diagonal(sel: ShardedElements, mesh: JMesh, c: float = 0.0,
                     vertex_layout: Optional[str] = None) -> jax.Array:
    """diag(A + cM) computed with the same sharded reduction.

    Layouts as in ``make_sharded_matvec``: replicated returns (nv,), owned
    returns (p, V) sharded in the halo-plan layout."""
    layout = _resolve_layout(sel, vertex_layout)
    spec_el = NamedSharding(mesh, P(AXIS))
    tets = jax.device_put(sel.tets, spec_el)
    grads = jax.device_put(sel.grads, spec_el)
    vol = jax.device_put(sel.vol, spec_el)

    def local_diag(t, g, v, nv):
        d = jnp.einsum("cid,cid->ci", g, g, precision=_HIGHEST) * v[:, None]
        if c != 0.0:
            d = d + c * 0.1 * v[:, None]
        return jax.ops.segment_sum(d.reshape(-1), t.reshape(-1),
                                   num_segments=nv)

    if layout == "replicated":
        nv = sel.n_verts

        def local(tets_l, grads_l, vol_l):
            y = local_diag(tets_l[0], grads_l[0], vol_l[0], nv)
            return jax.lax.psum(y, AXIS)

        return jax.jit(shard_map(local, mesh=mesh,
                                 in_specs=(P(AXIS),) * 3, out_specs=P()))(
            tets, grads, vol)

    plan = sel.halo
    send_idx = jax.device_put(plan.send_idx, spec_el)
    recv_idx = jax.device_put(plan.recv_idx, spec_el)

    def local_owned(tets_l, grads_l, vol_l, send_l, recv_l):
        y = local_diag(tets_l[0], grads_l[0], vol_l[0], plan.V)
        return halo_reduce(y, send_l[0], recv_l[0], AXIS)[None]

    return jax.jit(shard_map(local_owned, mesh=mesh,
                             in_specs=(P(AXIS),) * 5, out_specs=P(AXIS)))(
        tets, grads, vol, send_idx, recv_idx)


class OwnedOperators(NamedTuple):
    """An owned packing's PCG operators and the vertex layout they act
    in: ``halo`` is the packing's plan, or its locality-ordered
    relabelling where the windowed kernel runs (``fem.order``)."""
    matvec: Callable
    diag: jax.Array
    halo: HaloPlan


def make_owned_operators(sel: ShardedElements, mesh: JMesh, c: float = 0.0,
                         *, overlap: Optional[bool] = None,
                         use_pallas: Optional[bool] = None,
                         interpret: bool = False) -> OwnedOperators:
    """(matvec, diagonal, layout) for an owned-layout packing.

    Build once per packing and reuse across solves (e.g. every time step
    between repartitions) -- the closures carry the device-placed element
    and plan arrays, so rebuilding them per call re-places and re-traces
    for nothing.  ``overlap`` / ``use_pallas`` / ``interpret`` select the
    matvec hot path (see ``make_sharded_matvec``).  Where the kernel may
    run, the packing is first put in locality order (``fem.order``) and
    the kernel's windows are measured on it; if they fit, the operators
    act in that order, else the twin runs on the packing as given.  The
    diagonal is a once-per-packing setup cost and stays on the oracle
    pass.

    Under a telemetry tracer, sets the gauges ``fem_matvec.path``,
    ``fem_matvec.window_slots`` (the widest pass's ``W``),
    ``fem_matvec.tile_span_max`` and ``fem_matvec.order_s`` (host seconds
    of the ordering)."""
    overlap = _resolve_overlap(sel, overlap)
    if use_pallas is None:
        use_pallas = on_tpu()
    order_s = 0.0
    if use_pallas and (interpret or on_tpu()):      # the kernel may run
        t0 = time.perf_counter()
        ordered = order_packing(sel, mesh)
        order_s = time.perf_counter() - t0
        plan = matvec_plan(ordered, overlap=overlap, use_pallas=True,
                           interpret=interpret)
        if plan.path == "window":
            sel = ordered
    else:
        plan = matvec_plan(sel, overlap=overlap, use_pallas=use_pallas,
                           interpret=interpret)
    matvec, _ = make_sharded_matvec(sel, mesh, c, vertex_layout="owned",
                                    overlap=overlap, interpret=interpret,
                                    plan=plan)
    diag = sharded_diagonal(sel, mesh, c, vertex_layout="owned")
    metrics = telemetry.get_tracer().metrics
    metrics.gauge("fem_matvec.path",
                  help="window, xla or oracle").set(plan.path)
    metrics.gauge("fem_matvec.window_slots", unit="slots",
                  help="the kernel's window W").set(max(plan.windows,
                                                        default=0))
    metrics.gauge("fem_matvec.tile_span_max", unit="slots",
                  help="widest element tile's extent of slots").set(
                      plan.span)
    metrics.gauge("fem_matvec.order_s", unit="s",
                  help="host seconds of the locality order").set(order_s)
    return OwnedOperators(matvec, diag, sel.halo)


def sharded_solve_dirichlet(sel: ShardedElements, mesh: JMesh,
                            rhs: jax.Array, g: jax.Array, free: jax.Array,
                            c: float, *, tol: float = 1e-8,
                            maxiter: int = 2000,
                            operators: Optional[OwnedOperators] = None,
                            overlap: Optional[bool] = None,
                            use_pallas: Optional[bool] = None,
                            interpret: bool = False) -> CGResult:
    """Owned-layout distributed PCG solve of (A + cM) u = rhs, u = g on
    pinned dofs.

    The replicated-layout twin of ``fem.solve.solve_dirichlet``: takes
    the usual (n_verts,) ``rhs`` / boundary values ``g`` / ``free`` mask,
    converts them into the packing's (p, V) halo layout, runs PCG where
    every matvec communicates via ``halo_reduce`` (neighbor
    ``all_to_all``) and every inner product is a masked-by-ownership
    local reduction + one scalar psum, then assembles the solution back
    to (n_verts,).  No vertex-sized global collective anywhere in the
    iteration.

    ``operators``: prebuilt ``make_owned_operators(sel, mesh, c)``;
    callers solving repeatedly on the same packing should build them
    once and pass them in.  The solve runs in their layout
    (``OwnedOperators.halo``): ``to_local`` and ``from_local`` move the
    vectors into and out of it once per solve.  ``overlap`` /
    ``use_pallas`` / ``interpret`` select the matvec hot path when
    operators are built here (ignored when ``operators`` is passed --
    the prebuilt operators already chose).
    """
    if sel.layout != "owned" or sel.halo is None:
        raise ValueError("sharded_solve_dirichlet needs an owned-layout "
                         "packing (pass halo= to the packer)")
    if operators is None:
        operators = make_owned_operators(sel, mesh, c, overlap=overlap,
                                         use_pallas=use_pallas,
                                         interpret=interpret)
    matvec, diag_l, plan = operators
    sharding = NamedSharding(mesh, P(AXIS))
    owned = jax.device_put(plan.owned_mask, sharding)

    def local(a):
        return jax.lax.with_sharding_constraint(plan.to_local(a), sharding)

    # one program: run op by op, each of the setup's few dozen small ops
    # compiles on its own, and on a TPU that costs seconds apiece
    @jax.jit
    def solve(rhs, g, free):
        rhs_l, g_l, free_l = local(rhs), local(g), local(free)
        g_ext = jnp.where(free_l > 0, 0.0, g_l)
        lift = matvec(g_ext)
        b = jnp.where(free_l > 0, rhs_l - lift, 0.0)
        diag = jnp.where(free_l > 0, diag_l, 1.0)

        def op(u):
            au = matvec(u * free_l)
            return jnp.where(free_l > 0, au, u)

        res = pcg(op, b, diag, jnp.zeros_like(b), tol=tol, maxiter=maxiter,
                  vdot=owned_vdot(owned))
        x = plan.from_local(res.x + g_ext)
        # pinned dofs globally: vertices no leaf element references are in
        # no part's local list, but the replicated path still reports g there
        x = jnp.where(free > 0, x, g)
        return CGResult(x, res.iters, res.residual)

    return solve(jnp.asarray(rhs), jnp.asarray(g), jnp.asarray(free))
