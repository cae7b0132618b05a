"""Locality order of an owned packing: the slot and element order the
windowed matvec kernel needs.

The fused element kernel (``kernels.fem_matvec``) gathers and scatters
through one-hot products against a window of vertex slots per element
tile.  Its cost grows with the window, and the window has to cover every
slot the tile's elements touch.  In the packer's order (slots by global
id, elements as they arrive) a tile's slots spread over the whole part.
This module renumbers each part's slots by reverse Cuthill--McKee on the
part's vertex graph, which bounds the slots of any one element by the
graph's bandwidth, and sorts the elements by their smallest slot inside
each of the interface segment ``[0, n_interface)`` and the interior
segment, so consecutive elements share a narrow band of slots.  On the
long cylinder of paper Example 3.1 (519,840 tets, 96,400 slots) the
widest 256-element tile then spans 455 slots, inside a 1,024-slot
window.

The order is a relabelling: ``order_packing`` returns a packing whose
``HaloPlan`` carries the permuted slots (local vertex ids, owned mask,
global-to-local map and the halo send/receive indices), so
``HaloPlan.to_local`` / ``from_local`` move vectors into and out of the
new order in the gathers they already do once per solve, and nothing
runs per PCG iteration.  The plan's slot extent is also rounded up to
the lane multiple, so the kernel's resident vertex vector needs no pad.

Host/NumPy + SciPy (control plane, once per operator build); the
element payload is permuted on the device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh as JMesh
from jax.sharding import NamedSharding, PartitionSpec as P
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import reverse_cuthill_mckee

from ..distributed.sharding import shard_map
from ..kernels.fem_matvec import LANES
from .halo import HaloPlan

# the six edges of a tet, as corner pairs
_EDGES = np.array([(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)])


def rcm_slots(tets: np.ndarray, n: int) -> np.ndarray:
    """Reverse Cuthill--McKee order of slots ``[0, n)``: ``perm[new] = old``.

    ``tets``: (C, 4) slot ids; rows holding a slot >= ``n`` (padding) are
    left out.  The graph joins the slots of every element edge, so any
    element's slots lie within the order's bandwidth of each other."""
    t = tets[(tets < n).all(axis=1)].astype(np.int32)
    a = t[:, _EDGES[:, 0]].ravel()
    b = t[:, _EDGES[:, 1]].ravel()
    graph = csr_matrix((np.ones(2 * a.size, np.int8),
                        (np.concatenate([a, b]), np.concatenate([b, a]))),
                       shape=(n, n))
    return reverse_cuthill_mckee(graph, symmetric_mode=True).astype(np.int64)


def element_order(tets: np.ndarray, split: int) -> np.ndarray:
    """Element order of one part: by smallest slot, stably, inside
    ``[0, split)`` and inside ``[split, C)``, so no element changes
    segment.  Padding rows (slot ids all the pad slot) sort last in their
    segment."""
    key = tets.min(axis=1)
    head = np.argsort(key[:split], kind="stable")
    tail = split + np.argsort(key[split:], kind="stable")
    return np.concatenate([head, tail])


def _permuted_plan(plan: HaloPlan, inv: np.ndarray, V: int) -> HaloPlan:
    """The plan under the slot relabelling ``inv[s, old] = new`` (old pad
    ``plan.V`` maps to the new pad ``V``), with slot extent ``V``."""
    p = plan.p
    perm = np.full((p, V), plan.V, np.int64)      # new -> old, old pad V
    rows = np.arange(p)[:, None]
    perm[rows, inv[:, :plan.V]] = np.arange(plan.V)[None, :]
    lv = np.asarray(plan.local_verts)
    om = np.asarray(plan.owned_mask)
    lv = np.concatenate([lv, np.full((p, 1), plan.n_verts, lv.dtype)], 1)
    om = np.concatenate([om, np.zeros((p, 1), bool)], 1)

    def relabel(idx, parts):
        return inv[parts, np.asarray(idx)].astype(np.int32)

    return HaloPlan(
        local_verts=jnp.asarray(lv[rows, perm]),
        owned_mask=jnp.asarray(om[rows, perm]),
        global_to_local=jnp.asarray(
            relabel(plan.global_to_local, rows)),
        send_idx=jnp.asarray(relabel(plan.send_idx, rows[:, :, None])),
        recv_idx=jnp.asarray(relabel(plan.recv_idx, rows[:, :, None])),
        owner=plan.owner, p=p, n_verts=plan.n_verts, V=V, H=plan.H,
        n_local=plan.n_local, n_owned=plan.n_owned,
        n_ghost_total=plan.n_ghost_total)


@partial(jax.jit, static_argnums=(3,))
def _permute_payload(order, grads, vol, mesh):
    """Each part's element payload in its new order, on its device."""
    def local(o, g, v):
        return (jnp.take_along_axis(g, o[:, :, None, None], axis=1),
                jnp.take_along_axis(v, o, axis=1))
    parts = P(*mesh.axis_names)
    return shard_map(local, mesh=mesh, in_specs=(parts,) * 3,
                     out_specs=(parts,) * 2)(order, grads, vol)


def order_packing(sel, mesh: JMesh):
    """``sel`` (an owned ``ShardedElements``) in locality order: slots by
    reverse Cuthill--McKee per part, elements by smallest slot inside
    the interface and interior segments.  Same elements, same split
    point, same partition; only the labels and the element order move."""
    plan = sel.halo
    p, C = sel.p, sel.tets.shape[1]
    S = sel.n_interface or 0
    V = plan.V + (-plan.V) % LANES
    tets = np.asarray(sel.tets)
    inv = np.empty((p, plan.V + 1), np.int64)
    order = np.empty((p, C), np.int32)
    for s in range(p):
        n = plan.n_local[s]
        inv[s, :plan.V] = np.arange(plan.V)
        inv[s, plan.V] = V
        inv[s, rcm_slots(tets[s], n)] = np.arange(n)
        order[s] = element_order(inv[s, tets[s]], S)
    rows = np.arange(p)[:, None, None]
    new_tets = inv[rows, tets[np.arange(p)[:, None], order]]
    spec = NamedSharding(mesh, P(*mesh.axis_names))
    grads, vol = _permute_payload(jax.device_put(order, spec),
                                  sel.grads, sel.vol, mesh)
    return sel._replace(
        tets=jax.device_put(new_tets.astype(np.int32), spec),
        grads=grads, vol=vol, halo=_permuted_plan(plan, inv, V))
