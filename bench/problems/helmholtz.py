"""Paper Example 3.1: -div grad u + c u = f on the cylinder, with the
exact solution u = A cos(2 pi x) cos(2 pi y) cos(2 pi z) as Dirichlet
data, scaled by the amplitude A that the seed draws.

``program`` builds the problem object the program under test solves;
``exact`` and ``source`` are the reference's NumPy functions of the same
problem, which import nothing of the program.
"""
from __future__ import annotations

import functools

import numpy as np


def _scaled(fn, amplitude, x):
    return amplitude * fn(x)


def program(amplitude: float, c: float):
    """The program's ``HelmholtzProblem`` for u = amplitude * exact."""
    from repro.fem.problems import (HelmholtzProblem, helmholtz_exact,
                                    helmholtz_f)
    return HelmholtzProblem(
        c=c, exact=functools.partial(_scaled, helmholtz_exact, amplitude),
        f=functools.partial(_scaled, helmholtz_f, amplitude))


def exact(x: np.ndarray, amplitude: float) -> np.ndarray:
    w = 2.0 * np.pi
    return (amplitude * np.cos(w * x[..., 0]) * np.cos(w * x[..., 1])
            * np.cos(w * x[..., 2]))


def source(x: np.ndarray, amplitude: float, c: float) -> np.ndarray:
    return (12.0 * np.pi ** 2 + c) * exact(x, amplitude)
