"""A run with the timed path broken underneath comes out not correct: for
each fault a cell can have, the program is patched and the rest of the
run (set-up, window, check) goes as on the chip, the look for a chip
aside.  The four-chip cell runs on four virtual CPU devices, where the
exchange between them and the partition can break as well."""
import dataclasses

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


def _halo_leg_skipped(monkeypatch):
    """The matvec's halo exchange skips its first leg: owned slots miss
    the partial sums of the other parts' elements."""
    import jax

    import repro.fem.parallel as par

    def halo(y, send_idx, recv_idx, axis_name):
        back = jnp.where(recv_idx < y.shape[0],
                         y[jnp.minimum(recv_idx, y.shape[0] - 1)], 0.0)
        got = jax.lax.all_to_all(back, axis_name, split_axis=0,
                                 concat_axis=0, tiled=True)
        return y.at[send_idx.reshape(-1)].set(got.reshape(-1), mode="drop")
    monkeypatch.setattr(par, "halo_reduce", halo)


def _recut(monkeypatch, cut):
    """Each repartition's parts replaced by ``cut(parts in key order)``."""
    from repro.core.spec import Balancer
    orig = Balancer.balance

    def balance(self, weights, *, keys=None, **kw):
        res = orig(self, weights, keys=keys, **kw)
        order = np.argsort(np.asarray(keys), kind="stable")
        parts = np.asarray(res.parts).copy()
        parts[order] = cut(parts[order], self.spec.p)
        return dataclasses.replace(res, parts=jnp.asarray(parts))
    monkeypatch.setattr(Balancer, "balance", balance)


def _shifted_parts(monkeypatch):
    """The cuts moved half a run along the key order."""
    _recut(monkeypatch, lambda ps, p: np.roll(ps, len(ps) // (2 * p)))


def _heavy_part(monkeypatch):
    """The first part in key order takes a tenth of a run more elements."""
    def cut(ps, p):
        ps[:len(ps) * 11 // (10 * p)] = ps[0]
        return ps
    _recut(monkeypatch, cut)


FAULTS = {
    "helmholtz-cyl.adapt": [_unrefined, _altered_solution, _half_estimate],
    "helmholtz-cyl-p4.adapt": [_unrefined, _altered_solution, _half_estimate,
                               _halo_leg_skipped, _shifted_parts,
                               _heavy_part],
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
