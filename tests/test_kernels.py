"""Pallas kernels vs pure-jnp oracles (interpret=True), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.fem_matvec import (LANES, fem_element_matrices,
                                      fem_matvec_jnp, fem_matvec_pallas,
                                      fem_matvec_tiles, fem_matvec_window,
                                      fem_matvec_windows, lane_pad)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ksection_hist import (ksection_histogram_jnp,
                                         ksection_histogram_pallas)
from repro.kernels.prefix_scan import exclusive_scan_pallas
from repro.kernels.serve_prefill import (packed_attention_jnp,
                                         packed_attention_pallas)
from repro.kernels.sfc_keys import sfc_keys_pallas
from repro.kernels.ops import (exclusive_scan_op, fem_matvec_op,
                               flash_attention_op, ksection_histogram_op,
                               packed_attention_op, sfc_keys_op)

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n", [1024, 4096, 8192])
@pytest.mark.parametrize("curve", ["morton", "hilbert"])
def test_sfc_keys_kernel(n, curve):
    g = RNG.integers(0, 1024, (n, 3)).astype(np.int32)
    x, y, z = (jnp.asarray(g[:, i]) for i in range(3))
    got = sfc_keys_pallas(x, y, z, curve=curve, interpret=True)
    fn = ref.morton_keys_ref if curve == "morton" else ref.hilbert_keys_ref
    want = fn(jnp.asarray(g.astype(np.uint32))).astype(jnp.int32)
    assert (got == want).all()


@pytest.mark.parametrize("bits", [4, 8, 10])
def test_sfc_keys_kernel_bits(bits):
    g = RNG.integers(0, 1 << bits, (2048, 3)).astype(np.int32)
    x, y, z = (jnp.asarray(g[:, i]) for i in range(3))
    got = sfc_keys_pallas(x, y, z, curve="hilbert", bits=bits, interpret=True)
    want = ref.hilbert_keys_ref(jnp.asarray(g.astype(np.uint32)),
                                bits).astype(jnp.int32)
    assert (got == want).all()


@pytest.mark.parametrize("n", [1000, 1, 127, 1025, 8193, 0])
def test_sfc_keys_op_padding(n):
    """Item counts that are not multiples of the (8, 128) tile -- or of
    the multi-row grid step -- and zero-length inputs: the kernel pads to
    whole tiles and slices the keys back."""
    g = jnp.asarray(RNG.integers(0, 1024, (n, 3)).astype(np.uint32))
    got = sfc_keys_op(g, curve="hilbert", use_pallas=True, interpret=True)
    want = ref.hilbert_keys_ref(g)
    assert got.shape == (n,) and (got == want).all()


@pytest.mark.parametrize("n", [2048, 8192])
@pytest.mark.parametrize("scale", [1.0, 100.0])
def test_prefix_scan_kernel(n, scale):
    x = jnp.asarray((RNG.random(n) * scale).astype(np.float32))
    got = exclusive_scan_pallas(x, interpret=True)
    want = ref.exclusive_scan_ref(x)
    tol = 1e-5 * scale * n
    assert float(jnp.max(jnp.abs(got - want))) < tol


def test_prefix_scan_op_padding():
    x = jnp.asarray(RNG.random(3000).astype(np.float32))
    got = exclusive_scan_op(x, use_pallas=True, interpret=True)
    want = ref.exclusive_scan_ref(x)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-2


# --- ksection_hist ---------------------------------------------------------
# Integer-valued weights make every partial sum exact, so kernel, fused-jnp
# and searchsorted+segment_sum oracle must agree BIT-exactly, not allclose.

def _hist_case(n, m, seed=0, zero_frac=0.25):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.random(n).astype(np.float32))
    w = rng.integers(1, 10, n).astype(np.float32)
    w[rng.random(n) < zero_frac] = 0.0          # zero-weight items
    cuts = jnp.asarray(rng.random(m).astype(np.float32))  # UNSORTED
    return keys, jnp.asarray(w), cuts


@pytest.mark.parametrize("n,m", [(1024, 28), (1000, 56), (4096, 120),
                                 (37, 5), (2048, 1), (3000, 129),
                                 (1, 3), (8193, 300), (129, 257)])
def test_ksection_hist_kernel(n, m):
    """Fused kernel vs oracle, incl. non-multiple-of-tile n and m."""
    keys, w, cuts = _hist_case(n, m)
    got = ksection_histogram_pallas(keys, w, cuts, interpret=True)
    want = ref.ksection_histogram_ref(keys, w, cuts)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("n,m", [(1024, 28), (513, 40)])
def test_ksection_hist_jnp_matches_ref(n, m):
    keys, w, cuts = _hist_case(n, m, seed=1)
    got = ksection_histogram_jnp(keys, w, cuts)
    want = ref.ksection_histogram_ref(keys, w, cuts)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_ksection_hist_duplicate_keys_and_cuts():
    """Ties everywhere: repeated keys, repeated cuts, and cuts exactly
    equal to keys (the strict `key < cut` boundary)."""
    rng = np.random.default_rng(2)
    vals = np.array([0.1, 0.2, 0.2, 0.3, 0.5], np.float32)
    keys = jnp.asarray(vals[rng.integers(0, 5, 2000)])
    w = jnp.asarray(rng.integers(1, 5, 2000).astype(np.float32))
    cuts = jnp.asarray(np.array([0.2, 0.1, 0.2, 0.5, 0.05, 0.3, 0.3, 0.9],
                                np.float32))
    got = ksection_histogram_pallas(keys, w, cuts, interpret=True)
    want = ref.ksection_histogram_ref(keys, w, cuts)
    assert (np.asarray(got) == np.asarray(want)).all()
    # equal cuts get equal below-weight, whatever their positions
    g = np.asarray(got)
    assert g[0] == g[2] and g[5] == g[6]


def test_ksection_hist_sentinel_padded_tail():
    """The sharded pipeline pads shards by repeating the last item with
    weight 0: the tail must be invisible to every cut."""
    keys, w, cuts = _hist_case(900, 24, seed=3)
    pad = 1024 - 900
    keys_p = jnp.concatenate([keys, jnp.broadcast_to(keys[-1:], (pad,))])
    w_p = jnp.concatenate([w, jnp.zeros(pad, w.dtype)])
    got_p = ksection_histogram_pallas(keys_p, w_p, cuts, interpret=True)
    want = ref.ksection_histogram_ref(keys, w, cuts)
    assert (np.asarray(got_p) == np.asarray(want)).all()


def test_ksection_hist_empty_edges():
    """n=0 and m=0 return zeros like the oracle instead of crashing,
    through the kernel and through the op."""
    keys, w, cuts = _hist_case(64, 8, seed=5)
    empty_f = jnp.zeros((0,), jnp.float32)
    for fn in (lambda *a: ksection_histogram_pallas(*a, interpret=True),
               lambda *a: ksection_histogram_op(*a, use_pallas=True,
                                                interpret=True)):
        got = fn(empty_f, empty_f, cuts)
        assert got.shape == (8,) and not np.asarray(got).any()
        got = fn(keys, w, empty_f)
        assert got.shape == (0,)


def test_ksection_hist_op_dispatch():
    """Default on CPU runs the oracle exactly; use_pallas=True runs the
    kernel (interpret mode off-TPU) and still matches bit-for-bit."""
    keys, w, cuts = _hist_case(777, 21, seed=4)
    want = ref.ksection_histogram_ref(keys, w, cuts)
    assert (np.asarray(ksection_histogram_op(keys, w, cuts))
            == np.asarray(want)).all()
    got = ksection_histogram_op(keys, w, cuts, use_pallas=True,
                                interpret=True)
    assert (np.asarray(got) == np.asarray(want)).all()


# --- fem_matvec ------------------------------------------------------------
# Random "elements": slot ids in [0, V), random SPD-ish geometry, plus
# padding rows (slot n_out, zero grads/vol) exactly like the owned packing.

def _fem_case(C, V, seed=0, pad_frac=0.2, c=1.0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    n_pad = int(C * pad_frac)
    n = C - n_pad
    tets = rng.integers(0, V, (n, 4)).astype(np.int32)
    grads = rng.standard_normal((n, 4, 3)).astype(dtype)
    vol = (rng.random(n).astype(dtype) + 0.1)
    if n_pad:
        tets = np.concatenate([tets, np.full((n_pad, 4), V, np.int32)])
        grads = np.concatenate([grads, np.zeros((n_pad, 4, 3), dtype)])
        vol = np.concatenate([vol, np.zeros(n_pad, dtype)])
    u = rng.standard_normal(V + 1).astype(dtype)   # V slots + pad slot
    return (jnp.asarray(tets), jnp.asarray(grads), jnp.asarray(vol),
            jnp.asarray(u), V, c)


@pytest.mark.parametrize("C,V", [(1024, 256), (333, 100), (2048, 640),
                                 (7, 5), (256, 1), (129, 127), (385, 300)])
@pytest.mark.parametrize("c", [0.0, 1.0])
def test_fem_matvec_kernel(C, V, c):
    """Pallas kernel (interpret) vs geometry oracle over shapes including
    non-multiple-of-block C, tiny V, and padded element rows."""
    tets, grads, vol, u, n_out, _ = _fem_case(C, V, seed=C + int(c), c=c)
    kel = fem_element_matrices(grads, vol, c)
    got = fem_matvec_pallas(tets, kel, u, n_out, interpret=True)
    want = ref.fem_matvec_ref(tets, grads, vol, u, n_out, c=c)
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    assert got.shape == (n_out,)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale


def test_fem_matvec_jnp_twin_matches_ref():
    """The off-TPU fused-XLA twin agrees with the oracle (it is the
    production use_pallas=True CPU path)."""
    tets, grads, vol, u, n_out, c = _fem_case(1536, 400, seed=9)
    kel = fem_element_matrices(grads, vol, c)
    got = fem_matvec_jnp(tets, kel, u, n_out)
    want = ref.fem_matvec_ref(tets, grads, vol, u, n_out, c=c)
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale


def test_fem_matvec_empty_and_padding_invisible():
    """C=0 returns zeros; all-padding rows contribute exactly nothing."""
    u = jnp.asarray(RNG.standard_normal(65).astype(np.float32))
    kel0 = jnp.zeros((0, 4, 4), jnp.float32)
    out = fem_matvec_pallas(jnp.zeros((0, 4), jnp.int32), kel0, u, 64,
                            interpret=True)
    assert out.shape == (64,) and not np.asarray(out).any()
    tets = jnp.full((96, 4), 64, jnp.int32)        # every row -> pad slot
    kel = jnp.zeros((96, 4, 4), jnp.float32)
    out = fem_matvec_pallas(tets, kel, u, 64, interpret=True)
    assert not np.asarray(out).any()


def _banded_case(C, V, band, n_pad, seed, c):
    """Elements whose slots lie within ``band`` of a sorted base slot --
    the shape a locality-ordered packing has -- then ``n_pad`` padding
    rows (slot ``V``, zero geometry), shuffled in or left at the end."""
    rng = np.random.default_rng(seed)
    base = np.sort(rng.integers(0, max(1, V - band), C))
    tets = np.minimum(base[:, None] + rng.integers(0, band, (C, 4)), V - 1)
    grads = rng.standard_normal((C, 4, 3)).astype(np.float32)
    vol = rng.random(C).astype(np.float32) + 0.1
    tets = np.concatenate([tets, np.full((n_pad, 4), V)]).astype(np.int32)
    grads = np.concatenate([grads, np.zeros((n_pad, 4, 3), np.float32)])
    vol = np.concatenate([vol, np.zeros(n_pad, np.float32)])
    kel = fem_element_matrices(jnp.asarray(grads), jnp.asarray(vol), c)
    u = jnp.asarray(rng.standard_normal(V).astype(np.float32))
    return tets, jnp.asarray(grads), jnp.asarray(vol), kel, u


@pytest.mark.parametrize("case", [
    # (elements, slots, slot band, padding rows, block)
    (1024, 256, 256, 0, 256),      # W = Vp: the whole part in one window
    (2000, 5000, 300, 0, 256),     # W < Vp after ordering
    (1500, 3000, 200, 211, 256),   # ragged C with padding rows at the end
    (777, 1000, 90, 0, 128),       # first and last windows clamped at Vp
    (300, 129, 129, 17, 128),      # Vp two rows, every window whole
])
@pytest.mark.parametrize("c", [0.0, 1.0])
def test_fem_matvec_window_kernel(case, c):
    """The windowed kernel (interpret) vs the geometry oracle: windows
    from ``fem_matvec_windows`` hold every real slot of their tile, lie
    inside the lane-padded part, and padding rows stay invisible."""
    C, V, band, n_pad, block = case
    tets, grads, vol, kel, u = _banded_case(C, V, band, n_pad, C + V, c)
    starts, window, span = fem_matvec_windows(tets, V, block)
    vp = lane_pad(V)
    assert window % LANES == 0 and window <= vp
    if band + LANES < vp // 2:
        assert window < vp
    assert starts.min() >= 0 and (starts * LANES + window).max() <= vp
    t = np.concatenate([tets, np.full((-len(tets) % block, 4), V)])
    spans = []
    for k, tile in enumerate(t.reshape(-1, block * 4)):
        real = tile[tile < V]
        lo = starts[k] * LANES
        assert ((real >= lo) & (real < lo + window)).all()
        spans.append(real.max() - real.min() + 1 if real.size else 0)
    assert span == max(spans)
    ids, ks = fem_matvec_tiles(jnp.asarray(tets), kel, V, block)
    got = fem_matvec_window(ids, ks, jnp.asarray(starts), u, V,
                            window=window, interpret=True)
    want = ref.fem_matvec_ref(jnp.asarray(tets), grads, vol, u, V, c=c)
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    assert got.shape == (V,)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale
    # the whole-part kernel is the case W = Vp
    whole = fem_matvec_pallas(jnp.asarray(tets), kel, u, V, interpret=True,
                              block=block)
    assert float(jnp.max(jnp.abs(whole - want))) < 1e-4 * scale


def test_fem_matvec_window_gather_is_exact():
    """With a one-hot element matrix the matvec is a pure gather and
    scatter of single values: the three bf16 passes carry f32 through
    unrounded, so the result equals the input to the last bit."""
    V, C = 700, 700
    rng = np.random.default_rng(5)
    tets = np.stack([np.arange(C)] * 4, axis=1).astype(np.int32)
    kel = np.zeros((C, 4, 4), np.float32)
    kel[:, 0, 0] = 1.0                       # slot i receives u[i] once
    u = (rng.standard_normal(V) * 10.0 ** rng.integers(-20, 20, V)).astype(
        np.float32)
    starts, window, _ = fem_matvec_windows(tets, V, 128)
    ids, ks = fem_matvec_tiles(jnp.asarray(tets), jnp.asarray(kel), V, 128)
    got = fem_matvec_window(ids, ks, jnp.asarray(starts), jnp.asarray(u), V,
                            window=window, interpret=True)
    assert window < lane_pad(V)
    assert np.array_equal(np.asarray(got), u)


def test_fem_matvec_path_off_tpu():
    """Off the TPU the matvec kernel runs only when interpreted: the
    default is the oracle, ``use_pallas=True`` the XLA twin."""
    from repro.kernels.ops import fem_matvec_path
    assert fem_matvec_path(1000) == "oracle"
    assert fem_matvec_path(1000, use_pallas=True) == "xla"
    assert fem_matvec_path(1000, use_pallas=True, interpret=True) == "window"
    assert fem_matvec_path(150_000, use_pallas=True) == "xla"


def test_fem_matvec_op_dispatch():
    """use_pallas=False is bit-identical to the oracle; use_pallas=True +
    interpret runs the kernel through the Pallas interpreter; the default
    CPU twin path also lands within tolerance -- all through one op."""
    tets, grads, vol, u, n_out, c = _fem_case(512, 200, seed=4)
    want = ref.fem_matvec_ref(tets, grads, vol, u, n_out, c=c)
    got = fem_matvec_op(tets, grads, vol, u, n_out, c=c, use_pallas=False)
    assert (np.asarray(got) == np.asarray(want)).all()
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    for kw in (dict(interpret=True), dict()):
        got = fem_matvec_op(tets, grads, vol, u, n_out, c=c,
                            use_pallas=True, **kw)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale
    # precomputed element matrices short-circuit identically
    kel = fem_element_matrices(grads, vol, c)
    got = fem_matvec_op(tets, grads, vol, u, n_out, c=c, kel=kel,
                        use_pallas=True)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale


@pytest.mark.parametrize(
    "b,hq,hkv,s,d,causal,window",
    [(1, 4, 4, 256, 64, True, None),     # MHA causal
     (2, 8, 2, 256, 64, True, None),     # GQA
     (1, 4, 1, 512, 128, True, 256),     # MQA + sliding window
     (1, 2, 2, 256, 64, False, None),    # bidirectional
     (1, 4, 2, 384, 128, True, None)])   # non-pow2 seq
def test_flash_attention_kernel(b, hq, hkv, s, d, causal, window):
    q = jnp.asarray(RNG.standard_normal((b, hq, s, d)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)).astype(np.float32))
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 bq=128, bk=128, interpret=True)
    want = ref.mha_ref(q, k, v, causal=causal, window=window)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-3


def test_flash_attention_bf16():
    b, hq, hkv, s, d = 1, 4, 2, 256, 64
    q = jnp.asarray(RNG.standard_normal((b, hq, s, d)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    want = ref.mha_ref(q, k, v, causal=True)
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    assert float(err) < 3e-2


def test_ops_dispatch_to_ref_on_cpu():
    """Default (no pallas flag) on CPU runs the oracle path."""
    q = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)).astype(np.float32))
    out = flash_attention_op(q, q, q, causal=True)
    want = ref.mha_ref(q, q, q, causal=True)
    assert float(jnp.max(jnp.abs(out - want))) == 0.0


def _packed_case(lengths, C, hq, hkv, d):
    """Random packed-prefill attention problem: `lengths` requests laid
    back-to-back in a capacity-C buffer, tail padded with seg=-1."""
    assert sum(lengths) <= C
    q = jnp.asarray(RNG.standard_normal((hq, C, d)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((hkv, C, d)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((hkv, C, d)).astype(np.float32))
    seg = np.full(C, -1, np.int32)
    off = 0
    for sid, ln in enumerate(lengths):
        seg[off:off + ln] = sid
        off += ln
    return q, k, v, jnp.asarray(seg)


@pytest.mark.parametrize(
    "hq,hkv,d,C,lengths,softcap",
    [(4, 4, 64, 256, (64, 96, 32), None),        # MHA, padded tail
     (8, 2, 64, 256, (100, 60, 40, 56), None),   # GQA, full buffer
     (4, 1, 64, 144, (48, 80), None),            # MQA, C not block-mult
     (4, 2, 64, 256, (17, 3, 111, 64), 30.0)])   # softcap, ragged lens
def test_packed_attention_kernel(hq, hkv, d, C, lengths, softcap):
    q, k, v, seg = _packed_case(lengths, C, hq, hkv, d)
    want = ref.packed_attention_ref(q, k, v, seg, softcap=softcap)
    got_p = packed_attention_pallas(q, k, v, seg, softcap=softcap,
                                    interpret=True)
    got_j = packed_attention_jnp(q, k, v, seg, softcap=softcap)
    assert float(jnp.max(jnp.abs(got_p - want))) < 2e-3
    assert float(jnp.max(jnp.abs(got_j - want))) < 1e-4


def test_packed_attention_pad_rows_exactly_zero():
    """seg=-1 rows are outside every segment; all three implementations
    must emit exactly zero there (the paged scatter never reads them,
    but the contract keeps the parity check bitwise-meaningful)."""
    q, k, v, seg = _packed_case((40, 24), 128, 4, 2, 64)
    pad = np.asarray(seg) < 0
    assert pad.any()
    for out in (ref.packed_attention_ref(q, k, v, seg),
                packed_attention_jnp(q, k, v, seg),
                packed_attention_pallas(q, k, v, seg, interpret=True)):
        assert float(jnp.max(jnp.abs(out[:, pad]))) == 0.0


def test_packed_attention_matches_per_segment_mha():
    """Each segment of the packed output equals causal MHA run on that
    segment alone -- the packing is invisible to every request."""
    lengths = (56, 8, 40, 24)
    q, k, v, seg = _packed_case(lengths, 160, 4, 2, 64)
    got = packed_attention_jnp(q, k, v, seg)
    off = 0
    for ln in lengths:
        sl = slice(off, off + ln)
        want = ref.mha_ref(q[None, :, sl], k[None, :, sl], v[None, :, sl],
                           causal=True)[0]
        err = float(jnp.max(jnp.abs(got[:, sl] - want)))
        assert err < 1e-4, (sl, err)
        off += ln


def test_packed_attention_no_cross_segment_leakage():
    """Perturbing one request's K/V must not change any OTHER request's
    output at all -- the segment mask is the no-leakage guarantee."""
    lengths = (48, 48, 32)
    q, k, v, seg = _packed_case(lengths, 128, 4, 2, 64)
    segn = np.asarray(seg)
    k2 = jnp.where(jnp.asarray(segn == 1)[None, :, None], k * 13.0 + 7.0, k)
    v2 = jnp.where(jnp.asarray(segn == 1)[None, :, None], v * -5.0, v)
    others = jnp.asarray(segn != 1)
    for fn in (lambda *a: ref.packed_attention_ref(*a),
               lambda *a: packed_attention_jnp(*a),
               lambda *a: packed_attention_pallas(*a, interpret=True)):
        base, pert = fn(q, k, v, seg), fn(q, k2, v2, seg)
        assert (base[:, others] == pert[:, others]).all()


def test_packed_attention_op_dispatch():
    """use_pallas=False (and the CPU default) run the oracle bit-identically;
    use_pallas=True off-TPU runs the fused jnp twin."""
    q, k, v, seg = _packed_case((60, 36), 128, 4, 2, 64)
    want = ref.packed_attention_ref(q, k, v, seg)
    assert (packed_attention_op(q, k, v, seg, use_pallas=False)
            == want).all()
    assert (packed_attention_op(q, k, v, seg) == want).all()
    got = packed_attention_op(q, k, v, seg, use_pallas=True)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
