"""A run with the timed path broken underneath comes out not correct: for
each fault a cell can have, the program is patched and the rest of the
run (set-up, window, check) goes as on the chip, the look for a chip
aside.  The exchange between chips has no cell to break: no cell runs on
more than one chip."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.conftest import run_tiny


def _unrefined(monkeypatch):
    """The refinement step hands the mesh back unchanged."""
    import repro.fem.adapt as adapt
    monkeypatch.setattr(adapt, "refine", lambda mesh, marked: 0)


def _altered_solution(monkeypatch):
    """The solve's answer is off by a thousandth at one vertex."""
    import repro.fem.parallel as par
    orig = par.sharded_solve_dirichlet

    def solve(*a, **kw):
        r = orig(*a, **kw)
        k = int(jnp.argmax(jnp.abs(r.x)))
        return r._replace(x=r.x.at[k].multiply(1.001))
    monkeypatch.setattr(par, "sharded_solve_dirichlet", solve)


def _half_estimate(monkeypatch):
    """The estimator covers half of the elements; the rest read zero."""
    import repro.fem.adapt as adapt
    orig = adapt.zz_estimate

    def zz(el, u):
        eta = orig(el, u)
        return eta.at[eta.shape[0] // 2:].set(0.0)
    monkeypatch.setattr(adapt, "zz_estimate", zz)


FAULTS = {
    "helmholtz-cyl.adapt": [_unrefined, _altered_solution, _half_estimate],
}


@pytest.mark.parametrize("cell,fault", [(c, f) for c, fs in FAULTS.items()
                                        for f in fs],
                         ids=lambda x: getattr(x, "__name__", x))
def test_fault_is_not_correct(tiny, monkeypatch, cell, fault):
    fault(monkeypatch)
    out = run_tiny(tiny, cell)
    assert not out["correct"], out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
    assert np.isfinite([c["value"] for c in out["checks"].values()]).all()
