"""The partition the balance stage has to produce, restated in NumPy.

PHG's HSFC partitioner (the paper, sections 2.2-2.3): each element's
barycentre is mapped into the grid ``[0, 2**bits)**3`` by the uniform
box map ``(x - lo) / max extent`` and keyed by its index along the
Hilbert curve (Skilling, "Programming the Hilbert curve", 2004); the
elements, in key order, are cut into ``p`` runs of equal count.  The box
is the first cut's barycentre box, held fixed until the live box drifts
past ``DRIFT`` of its extent, and then taken anew.  After a refinement
each element inherits the part of the element that held it, and the mesh
is cut afresh only where that inherited partition's largest part exceeds
the mean by more than the configuration's trigger (the paper's policy).
Parts are compared up to a relabelling, since the program renumbers its
parts to keep elements where they were.

The per-axis box map (``uniform=False``, Zoltan's, which the paper
measures against) stands in for the control: the same cut of a
distorted domain.
"""
from __future__ import annotations

import numpy as np
from scipy.optimize import linear_sum_assignment
from scipy.spatial import cKDTree

from bench.reference.mesh import centroids

BITS = 10
DRIFT = 0.05


def grid(x: np.ndarray, lo: np.ndarray, hi: np.ndarray, *,
         uniform: bool = True, bits: int = BITS) -> np.ndarray:
    """(n, 3) points -> (n, 3) integer cells of the box map: one scale for
    every axis (``uniform``), or each axis's own."""
    extent = np.where(hi - lo > 0, hi - lo, 1.0)
    unit = (x - lo) / (extent.max() if uniform else extent)
    return np.clip(np.floor(unit * (1 << bits)), 0,
                   (1 << bits) - 1).astype(np.int64)


def hilbert_keys(cells: np.ndarray, bits: int = BITS) -> np.ndarray:
    """Skilling's AxesToTranspose, then the transpose read out with the
    first axis's bit most significant in each triple."""
    x = [cells[:, i].copy() for i in range(3)]
    q = 1 << (bits - 1)
    while q > 1:                        # undo excess work
        low = q - 1
        for i in range(3):
            hit = (x[i] & q) != 0
            swap = (x[0] ^ x[i]) & low
            if i == 0:
                x[0] = np.where(hit, x[0] ^ low, x[0])
            else:
                x[0], x[i] = (np.where(hit, x[0] ^ low, x[0] ^ swap),
                              np.where(hit, x[i], x[i] ^ swap))
        q >>= 1
    for i in (1, 2):                    # Gray encode
        x[i] = x[i] ^ x[i - 1]
    t = np.zeros_like(x[0])
    q = 1 << (bits - 1)
    while q > 1:
        t = np.where((x[2] & q) != 0, t ^ (q - 1), t)
        q >>= 1
    return _interleave([xi ^ t for xi in x], bits)


def _interleave(x, bits: int) -> np.ndarray:
    key = np.zeros(len(x[0]), np.int64)
    for b in range(bits):
        for i in range(3):
            key |= ((x[i] >> b) & 1) << (3 * b + 2 - i)
    return key


class FrozenBox:
    """The key box: the first points' box, kept while every corner of the
    live box stays within ``DRIFT`` of its largest extent."""

    def __init__(self):
        self.lo = self.hi = None

    def __call__(self, x: np.ndarray):
        lo, hi = x.min(axis=0), x.max(axis=0)
        if self.lo is not None:
            extent = float((self.hi - self.lo).max()) or 1.0
            drift = max(np.abs(lo - self.lo).max(),
                        np.abs(hi - self.hi).max()) / extent
            if drift <= DRIFT:
                return self.lo, self.hi
        self.lo, self.hi = lo, hi
        return lo, hi


def equal_runs(keys: np.ndarray, p: int) -> np.ndarray:
    """Part of each element: its rank in stable key order, cut into ``p``
    runs whose counts differ by at most one."""
    order = np.argsort(keys, kind="stable")
    parts = np.empty(len(keys), np.int64)
    parts[order] = np.arange(len(keys)) * p // len(keys)
    return parts


def parents(v0, t0, v1, t1) -> np.ndarray:
    """For each element of the refined mesh ``(v1, t1)``, the element of
    ``(v0, t0)`` that holds it: the one whose barycentric coordinates of
    the child's barycentre are least negative, searched among the nearest
    barycentres, more of them where none holds it."""
    x0 = v0[t0[:, 0]]
    inv = np.linalg.inv(np.swapaxes(v0[t0[:, 1:]] - x0[:, None], 1, 2))
    c1 = centroids(v1, t1)
    tree = cKDTree(centroids(v0, t0))
    out = np.empty(len(t1), np.int64)
    todo = np.arange(len(t1))
    for k in (8, 32, 128):
        for chunk in np.array_split(todo, -(-len(todo) // 65536)):
            _, cand = tree.query(c1[chunk], k=min(k, len(t0)))
            cand = cand.reshape(len(chunk), -1)
            lam = np.einsum("nkij,nkj->nki", inv[cand],
                            c1[chunk, None] - x0[cand])
            inside = np.minimum(lam.min(axis=2), 1.0 - lam.sum(axis=2))
            best = inside.argmax(axis=1)
            out[chunk] = cand[np.arange(len(chunk)), best]
            inside = inside[np.arange(len(chunk)), best]
            todo = np.setdiff1d(todo, chunk[inside > -1e-9])
        if not len(todo):
            break
    return out


def partitions(meshes, p: int, trigger: float, *,
               uniform: bool = True) -> list:
    """The parts of each mesh of a run of refinements, ``meshes`` being
    ``(verts, tets)`` pairs, each refined from the one before."""
    box = FrozenBox()
    out = []
    for s, (v, t) in enumerate(meshes):
        if s:
            inherited = out[-1][parents(*meshes[s - 1], v, t)]
            if imbalance(inherited, p) + 1.0 <= trigger:
                out.append(inherited)
                continue
        x = centroids(v, t)
        out.append(equal_runs(
            hilbert_keys(grid(x, *box(x), uniform=uniform)), p))
    return out


def part_gap(parts: np.ndarray, ref: np.ndarray, p: int) -> float:
    """Share of elements whose part differs from the reference's under the
    relabelling of the parts that agrees best; a label outside ``[0, p)``
    or a missing element counts as differing."""
    parts, ref = np.asarray(parts), np.asarray(ref)
    if parts.shape != ref.shape:
        return 1.0
    ok = (parts >= 0) & (parts < p)
    overlap = np.zeros((p, p), np.int64)
    np.add.at(overlap, (parts[ok], ref[ok]), 1)
    rows, cols = linear_sum_assignment(overlap, maximize=True)
    return 1.0 - overlap[rows, cols].sum() / len(ref)


def imbalance(parts: np.ndarray, p: int) -> float:
    """Largest part's count over the mean count, minus one."""
    parts = np.asarray(parts)
    counts = np.bincount(parts[(parts >= 0) & (parts < p)], minlength=p)
    return float(counts.max() * p / len(parts) - 1.0)
