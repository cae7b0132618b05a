"""The reference partition (``bench/reference/balance.py``) on its own:
the Hilbert keys, the equal cut, the frozen box and the parent lookup."""
import numpy as np
import pytest

from bench.reference import balance as rb
from bench.reference.mesh import kuhn_cylinder


def test_keys_follow_a_shuffle_and_the_cut_is_even():
    rng = np.random.default_rng(7)
    x = rng.random((300, 3)) * [4.0, 1.0, 1.0]
    cells = rb.grid(x, x.min(axis=0), x.max(axis=0))
    keys = rb.hilbert_keys(cells)
    perm = rng.permutation(len(x))
    np.testing.assert_array_equal(rb.hilbert_keys(cells[perm]), keys[perm])
    for p in (1, 3, 4, 7):
        counts = np.bincount(rb.equal_runs(keys, p), minlength=p)
        assert counts.max() - counts.min() <= 1 and counts.sum() == len(x)


def test_hilbert_keys_walk_between_neighbouring_cells():
    cells = np.stack(np.meshgrid(*[np.arange(8)] * 3, indexing="ij"),
                     axis=-1).reshape(-1, 3)
    keys = rb.hilbert_keys(cells, bits=3)
    np.testing.assert_array_equal(np.sort(keys), np.arange(512))
    walk = cells[np.argsort(keys)]
    assert (np.abs(np.diff(walk, axis=0)).sum(axis=1) == 1).all()


def test_box_stays_frozen_until_it_drifts():
    box = rb.FrozenBox()
    x = np.array([[0.0, 0.0, 0.0], [4.0, 1.0, 1.0]])
    lo, hi = box(x)
    assert box(x + 0.19)[0] is lo          # 0.19 / 4 is under 5 %
    np.testing.assert_array_equal(box(x + 0.21)[0], lo + 0.21)


def test_parents_of_a_bisection():
    v, t = kuhn_cylinder(2, 1, 2.0, 0.5)
    k = 5
    mid = len(v)
    v1 = np.vstack([v, v[t[k, :2]].mean(axis=0)])
    a, b, c, d = t[k]
    t1 = np.vstack([t, [[mid, b, c, d]]])
    t1[k] = [a, mid, c, d]
    want = np.arange(len(t1))
    want[-1] = k
    np.testing.assert_array_equal(rb.parents(v, t, v1, t1), want)


@pytest.mark.parametrize("labels,gap", [([1, 1, 0, 0, 2, 2], 0.0),
                                        ([0, 0, 1, 1, 1, 2], 1 / 6),
                                        ([0, 0, 1, 1, 2, 5], 1 / 6)])
def test_part_gap_forgives_a_relabelling_only(labels, gap):
    ref = np.array([0, 0, 1, 1, 2, 2])
    assert rb.part_gap(np.array(labels), ref, 3) == pytest.approx(gap)
