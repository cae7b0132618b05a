"""Kuhn-triangulated cylinder, the domain of the paper's Example 3.1.

A box of ``n_axial x n_cross x n_cross`` cubes, six Kuhn tetrahedra per
cube along the vertex-permutation paths from the cube's (0,0,0) corner to
its (1,1,1) corner, with the square cross-section mapped onto the disk by
the elliptical square-to-disk map.  Vertices are numbered in C order of
their (i, j, k) grid index; tetrahedra cube by cube in C order, the six
paths of a cube in lexicographic order of their axis permutations.
"""
from __future__ import annotations

import itertools

import numpy as np

PERMS = list(itertools.permutations(range(3)))


def kuhn_cylinder(n_axial: int, n_cross: int, length: float,
                  radius: float) -> tuple[np.ndarray, np.ndarray]:
    """(verts (nv, 3) float64, tets (nt, 4) int64)."""
    shape = np.array([n_axial + 1, n_cross + 1, n_cross + 1])
    axes = [np.linspace(0.0, 1.0, n) for n in shape]
    x, y, z = np.meshgrid(*axes, indexing="ij")
    x = x.ravel() * length
    y = (y.ravel() * 2.0 - 1.0)
    z = (z.ravel() * 2.0 - 1.0)
    verts = np.stack([x, y * np.sqrt(np.maximum(0.0, 1.0 - z * z / 2.0)),
                      z * np.sqrt(np.maximum(0.0, 1.0 - y * y / 2.0))],
                     axis=1)
    verts[:, 1:] *= radius
    cubes = np.stack(np.meshgrid(np.arange(n_axial), np.arange(n_cross),
                                 np.arange(n_cross), indexing="ij"),
                     axis=-1).reshape(-1, 3)
    stride = np.array([shape[1] * shape[2], shape[2], 1])
    tets = np.empty((cubes.shape[0], len(PERMS), 4), np.int64)
    for t, perm in enumerate(PERMS):
        corner = cubes.copy()
        tets[:, t, 0] = corner @ stride
        for step, axis in enumerate(perm, start=1):
            corner[:, axis] += 1
            tets[:, t, step] = corner @ stride
    return verts, tets.reshape(-1, 4)


def centroids(verts: np.ndarray, tets: np.ndarray) -> np.ndarray:
    return verts[tets].mean(axis=1)
