"""P1 finite elements for -div grad u + c u = f with Dirichlet data.

The plain reference of the adaptive step: assembly into a sparse matrix,
a float64 solve, the Zienkiewicz--Zhu estimator and Doerfler marking,
each as the textbook states it.  The problem's data (``f`` and the
boundary values ``g``) come from its file under ``bench/problems``.  ``lowp`` selects the control: the same
solve and estimator in float32 with every product taken as three bf16
passes (what XLA's ``Precision.HIGH`` does on a TPU), the precision step
below float32 at ``HIGHEST``.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla

TET_FACES = np.array([[1, 2, 3], [0, 2, 3], [0, 1, 3], [0, 1, 2]])
# degree-2 four-point rule on the tetrahedron, barycentric, weights V/4
QA, QB = 0.5854101966249685, 0.13819660112501053
QPTS = np.full((4, 4), QB) + np.eye(4) * (QA - QB)


def geometry(verts: np.ndarray, tets: np.ndarray):
    """Gradients of the four barycentric functions (nt, 4, 3), volumes."""
    x = verts[tets]
    b = np.swapaxes(x[:, 1:] - x[:, :1], 1, 2)       # columns x_j - x_0
    inv = np.linalg.inv(b)                            # rows grad lam_1..3
    g = np.concatenate([-inv.sum(axis=1, keepdims=True), inv], axis=1)
    return g, np.abs(np.linalg.det(b)) / 6.0


def element_matrices(grads, vol, c: float) -> np.ndarray:
    mass = (np.ones((4, 4)) + np.eye(4)) / 20.0
    return (np.einsum("tid,tjd->tij", grads, grads) + c * mass) \
        * vol[:, None, None]


def boundary_vertices(tets: np.ndarray) -> np.ndarray:
    faces = np.sort(tets[:, TET_FACES].reshape(-1, 3), axis=1)
    f, counts = np.unique(faces, axis=0, return_counts=True)
    return np.unique(f[counts == 1])


def load_vector(verts, tets, vol, f) -> np.ndarray:
    xq = np.einsum("qb,tbd->tqd", QPTS, verts[tets])
    fq = f(xq)                                        # (nt, 4 points)
    contrib = (fq @ QPTS) * (vol[:, None] / 4.0)      # (nt, 4 corners)
    return np.bincount(tets.ravel(), contrib.ravel(), len(verts))


# -- the control's arithmetic -------------------------------------------

def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest, ties to even), kept in f32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def split3(x: np.ndarray):
    hi = to_bf16(x)
    return hi, to_bf16(np.float32(x) - hi)


def mul_high(a, b: np.ndarray) -> np.ndarray:
    """a * b as a TPU computes it at Precision.HIGH: bf16 pieces, three
    products, float32 sums.  ``a`` may be given already split."""
    ah, al = a if isinstance(a, tuple) else split3(a)
    bh, bl = split3(b)
    return (ah * bh + (ah * bl + al * bh)).astype(np.float32)


# -- solve ------------------------------------------------------------------

REF_RTOL = 1e-13


def solve(verts, tets, f, g, c: float, *, tol: float, lowp: bool = False,
          maxiter: int = 20000) -> tuple[np.ndarray, int]:
    """u with u = g on the boundary.  float64: Jacobi-preconditioned CG
    down to a relative residual of ``REF_RTOL``, far below what float32
    can reach.  lowp: the Jacobi-preconditioned CG of the configuration
    (``tol``) in float32 at HIGH."""
    grads, vol = geometry(verts, tets)
    ke = element_matrices(grads, vol, c)
    nv = len(verts)
    bnd = boundary_vertices(tets)
    free = np.ones(nv, bool)
    free[bnd] = False
    rhs = load_vector(verts, tets, vol, f)
    ug = np.where(free, 0.0, g)
    if not lowp:
        rows = np.repeat(tets, 4, axis=1).ravel()
        cols = np.tile(tets, (1, 4)).ravel()
        a = sp.csr_matrix((ke.ravel(), (rows, cols)), shape=(nv, nv))
        b = rhs - a @ ug
        fi = np.flatnonzero(free)
        af = a[fi][:, fi].tocsr()
        jacobi = sp.diags(1.0 / af.diagonal())
        it = [0]

        def count(_):
            it[0] += 1
        x, info = spla.cg(af, b[fi], rtol=REF_RTOL, atol=0.0, M=jacobi,
                          maxiter=maxiter, callback=count)
        if info != 0:
            raise RuntimeError(f"reference CG stopped unconverged ({info})")
        u = ug.copy()
        u[fi] = x
        return u, it[0]
    ke32 = ke.astype(np.float32)
    ke_split = split3(ke32)
    t = tets

    def matvec(v):
        out = mul_high(ke_split, v[t][:, None, :]).sum(axis=2,
                                                       dtype=np.float32)
        return np.bincount(t.ravel(), out.ravel(), nv).astype(np.float32)

    def dot(x, y):
        return np.float32(mul_high(x, y).sum(dtype=np.float32))

    fm = free.astype(np.float32)
    b = np.where(free, rhs - matvec(ug.astype(np.float32)), 0.0) \
        .astype(np.float32)
    diag = np.bincount(t.ravel(), np.einsum("tii->ti", ke32).ravel(), nv)
    inv_d = np.where(free, 1.0 / diag, 0.0).astype(np.float32)

    def op(v):
        return np.where(free, matvec(v * fm), v).astype(np.float32)

    x = np.zeros(nv, np.float32)
    r = b.copy()
    z = mul_high(r, inv_d)
    p = z.copy()
    rz = dot(r, z)
    bnorm = max(np.sqrt(dot(b, b)), 1e-30)
    it = 0
    while np.sqrt(max(dot(r, r), 0.0)) > tol * bnorm and it < maxiter:
        ap = op(p)
        alpha = rz / max(dot(p, ap), 1e-30)
        x = (x + mul_high(np.float32(alpha), p)).astype(np.float32)
        r = (r - mul_high(np.float32(alpha), ap)).astype(np.float32)
        z = mul_high(r, inv_d)
        rz_new = dot(r, z)
        p = (z + mul_high(np.float32(rz_new / max(rz, 1e-30)), p)) \
            .astype(np.float32)
        rz = rz_new
        it += 1
    return np.where(free, x, g).astype(np.float64), it


# -- estimate and mark --------------------------------------------------------

def zz_estimate(verts, tets, u, *, lowp: bool = False) -> np.ndarray:
    """eta_T = (|T|/4 sum_vertices |G(v) - grad u_T|^2)^(1/2), G the
    volume-weighted average of the element gradients at each vertex."""
    grads, vol = geometry(verts, tets)
    nv = len(verts)
    if lowp:
        gr = grads.astype(np.float32)
        ue = np.asarray(u, np.float32)[tets]
        gt = mul_high(gr, ue[:, :, None]).sum(axis=1, dtype=np.float32)
        vol = vol.astype(np.float32)
    else:
        gt = np.einsum("tid,ti->td", grads, np.asarray(u, np.float64)[tets])
    den = np.bincount(tets.ravel(), np.repeat(vol, 4), nv)
    node = np.stack([np.bincount(tets.ravel(), np.repeat(gt[:, d] * vol, 4),
                                 nv) / np.maximum(den, 1e-300)
                     for d in range(3)], axis=1)
    diff = node[tets] - gt[:, None, :]
    return np.sqrt((diff * diff).sum(axis=(1, 2)) * vol / 4.0)


def doerfler(eta: np.ndarray, theta: float) -> np.ndarray:
    """Smallest set holding theta of sum eta^2, largest indicators first."""
    e2 = np.asarray(eta, np.float64) ** 2
    order = np.argsort(-e2, kind="stable")
    k = int(np.searchsorted(np.cumsum(e2[order]), theta * e2.sum())) + 1
    marked = np.zeros(len(e2), bool)
    marked[order[:k]] = True
    return marked


# -- the refined mesh ---------------------------------------------------------

def boundary_area(verts, tets) -> float:
    faces = np.sort(tets[:, TET_FACES].reshape(-1, 3), axis=1)
    f, counts = np.unique(faces, axis=0, return_counts=True)
    f = f[counts == 1]
    x = verts[f]
    return float(0.5 * np.linalg.norm(
        np.cross(x[:, 1] - x[:, 0], x[:, 2] - x[:, 0]), axis=1).sum())


def refinement_faults(tets0, marked0, tets1) -> int:
    """Marked elements of the coarse mesh still present, unsplit, in the
    refined one, plus faces of the refined mesh shared by more than two
    elements."""
    key = lambda t: {tuple(r) for r in np.sort(t, axis=1).tolist()}
    survivors = key(tets0[marked0]) & key(tets1)
    faces = np.sort(tets1[:, TET_FACES].reshape(-1, 3), axis=1)
    _, counts = np.unique(faces, axis=0, return_counts=True)
    return len(survivors) + int((counts > 2).sum())
