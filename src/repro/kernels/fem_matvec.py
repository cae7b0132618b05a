"""Pallas TPU kernel: fused P1 element matvec (gather -> apply -> scatter).

Paper mapping (section 1, the compute model): the distributed FEM
operator is element-local -- gather the 4 vertex values of each tet,
apply the 4x4 element stiffness (+ optional mass) matrix, scatter the 4
results back into the vertex vector.  The *communication* of that
matvec is cut-proportional (`fem.halo`), so the remaining per-call cost
is exactly this gather/apply/scatter streak over the local elements.
Its XLA form (``fem_matvec_jnp``) is a scalar gather and a
``segment_sum`` scatter-add over every corner, and on a TPU the scatter
serialises.

The kernel restructures the hot path around three ideas:

* **precomputed element matrices**: the per-element 4x4 operator
  ``K_e = (g g^T + c M) |e|`` is constant across matvecs (PCG calls the
  operator hundreds of times per solve on a fixed mesh), so it is built
  once per packing (`fem_element_matrices`) and streamed;
* **one launch, no scatter**: element tiles stream HBM->VMEM (one grid
  step per tile) against the VMEM-resident vertex vector, gather and
  scatter-accumulate are one-hot matmuls on the MXU, and the resident
  output block is the accumulator across the serialised grid steps;
* **a window per tile**: a tile's one-hot covers only the ``W`` slots
  from its window start (scalar-prefetched, a lane multiple), not the
  whole part.  Ordered by locality (``fem.order``: slots by reverse
  Cuthill--McKee, elements by smallest slot) every tile of a 96k-slot
  part fits a 1,024-slot window, and the work per element no longer
  grows with the part.  The whole-part kernel is the case ``W = Vp``.

Layout: the vertex vector is resident as (Vp / 128, 128) rows of slots.
A tile's (W, block) one-hot of one corner factors into a lane one-hot
(128, block) -- slot mod 128 -- and a row mask (W / 128, block); the
four corners share the lanes axis as one (1, 4 * block) id row.  Gather:
``rows (W/128, 128) @ lanes (128, 4 block)`` picks each element's lane
in every window row, and the row mask keeps its row.  Scatter: the
masked (W/128, 4 block) contributions contract with the lane one-hot
back to (W/128, 128) window rows.  The one-hots are bf16 (0/1 is exact)
and each f32 operand goes through the MXU as three exact bf16 pieces,
so the gather is exact and the scatter rounds only in its f32 sums.

VMEM (``fem_matvec_fits``): the lane one-hot per tile, the row-sized
intermediates per window row, and the two resident vertex blocks.
``ops.fem_matvec_path`` runs the kernel where the window fits and the
XLA gather/scatter twin ``fem_matvec_jnp`` on the device where it does
not, decided from shapes.

Contract (assignment): ``ops.fem_matvec_op`` is the public wrapper
(oracle fallback off-TPU, interpret mode on CPU when requested);
``ref.fem_matvec_ref`` is the gather/einsum/segment_sum oracle;
``fem_matvec_jnp`` is the kernel's precomputed-K math as fused XLA ops.
Parity is asserted in interpret mode over shape/window sweeps in
``tests/test_kernels.py``.  Accumulation order differs from the oracle
(per-slot partial sums per tile instead of one global segment_sum), so
float parity is tolerance-exact, not bit-exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_C = 256    # elements per HBM->VMEM tile (a lane multiple)
LANES = 128      # vertex-axis padding multiple (VPU lane count)
# VMEM the kernel's working set may take (``fem_matvec_fits``): half the
# 16 MiB scoped-VMEM default of a TPU v5e core, leaving room for the
# pipeline's double-buffered element tiles
VMEM_BUDGET = 8 * 1024 * 1024

# f32 arithmetic stays f32 on a TPU: there XLA's default precision runs
# dot-like einsums as single bf16 passes
_HIGHEST = jax.lax.Precision.HIGHEST

# P1 reference-tet mass matrix scaled by 20 (kept integer-exact; the
# caller multiplies by vol/20) -- mirrors fem.assemble._MASS * 20.
_MASS20 = np.full((4, 4), 1.0, np.float64) + np.eye(4)


def gather_corners(u: jax.Array, tets: jax.Array) -> jax.Array:
    """``u[tets]`` for (..., 4) connectivity, gathered corner by corner.

    The same values as ``u[tets]``.  On a TPU, XLA's gather with an index
    array whose minor dimension is 4 is pathological: at 1.1M elements
    into 200k vertices it took 157 s to compile and its cost model read
    580 GB, where the gather over the (4, ...) transpose compiled in
    1.4 s and read 18 GB (v5e compiled from a described topology)."""
    return jnp.moveaxis(u[jnp.moveaxis(tets, -1, 0)], 0, -1)


@functools.partial(jax.jit, static_argnums=2)
def fem_element_matrices(grads: jax.Array, vol: jax.Array,
                         c: float = 0.0) -> jax.Array:
    """Per-element 4x4 operator ``K_e = (grad_j . grad_i + c M_ji) |e|``.

    ``grads``: (..., C, 4, 3), ``vol``: (..., C) -> (..., C, 4, 4).
    Constant across matvecs on a fixed packing -- build once, stream
    per call.  Padding elements (grads = 0, vol = 0) get K_e = 0, so
    they are no-ops wherever their slot ids point.  Elementwise: the
    batched einsum read 15x the bytes and took 4.5 GB of scratch at 1.1M
    elements on a TPU v5e (compiled from a described topology)."""
    k = jnp.stack([jnp.stack([jnp.sum(grads[..., i, :] * grads[..., j, :],
                                      axis=-1) for j in range(4)], axis=-1)
                   for i in range(4)], axis=-2)
    if c != 0.0:
        mass = jnp.asarray(_MASS20 / 20.0, k.dtype)
        k = k + c * mass
    return k * vol[..., None, None]


def lane_pad(n: int) -> int:
    """``n`` slots rounded up to the lane multiple (at least one row)."""
    return max(LANES, n + (-n) % LANES)


def _split3(a):
    """f32 ``a`` as three bf16 pieces whose f32 sum is ``a`` exactly: each
    takes the next 8 bits of the 24-bit significand."""
    hi = a.astype(jnp.bfloat16)
    rest = a - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _sum3(x, rows):
    """Sum the three row blocks a product of ``_split3`` pieces gives."""
    return (x[:rows] + x[rows:2 * rows]) + x[2 * rows:]


def _window_kernel(start_ref, t_ref, k_ref, u_ref, out_ref, *, rows):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    r0 = start_ref[i]                    # the tile's first window row
    b4 = t_ref.shape[1]                  # t: (1, 4B) corner-major slot ids
    B = b4 // 4                          # k: (16, B) K_e rows, j*4+i major
    rel = t_ref[...] - r0 * LANES        # slot offset into the window
    # lane one-hot (128, 4B) and row mask (rows, 4B): their product is the
    # (W, 4B) one-hot of the window.  A slot outside the window (padding)
    # matches no row and drops.  0/1 is exact in bf16.
    lanes = jax.lax.broadcasted_iota(jnp.int32, (LANES, b4), 0)
    onehot = (lanes == (rel & (LANES - 1))).astype(jnp.bfloat16)
    hit = jax.lax.broadcasted_iota(jnp.int32, (rows, b4), 0) == (rel >> 7)

    def mm(a, contract):
        # three exact bf16 pieces of `a` against the 0/1 lanes in one
        # product; the MXU multiplies bf16 exactly and accumulates in f32
        return _sum3(jax.lax.dot_general(
            _split3(a), onehot, (((1,), (contract,)), ((), ())),
            preferred_element_type=jnp.float32), rows)

    # gather: every window row's value at each id's lane, then its row
    window = u_ref[pl.ds(r0, rows), :]                         # (rows, 128)
    ue = jnp.sum(jnp.where(hit, mm(window, 0), 0.0), axis=0,
                 keepdims=True)                                 # (1, 4B)
    k = k_ref[...]
    au = jnp.concatenate(
        [sum(k[4 * j + c:4 * j + c + 1] * ue[:, c * B:(c + 1) * B]
             for c in range(4)) for j in range(4)], axis=1)     # (1, 4B)
    # scatter: each contribution on its row, summed into its lane
    out_ref[pl.ds(r0, rows), :] += mm(jnp.where(hit, au, 0.0), 1)


def fem_matvec_vmem_bytes(window: int, n_out: int,
                          block: int = BLOCK_C) -> int:
    """VMEM the windowed kernel holds: per tile the (128, 4 block) lane
    one-hot with its iota and compare mask (10 B an entry), per window
    row of 128 slots twelve (1, 4 block) f32 intermediates (the row mask,
    the three gathered products and their sum, the masked scatter operand
    and its bf16 pieces), and the (Vp / 128, 128) vertex input and output
    accumulator, each double-buffered."""
    b4 = 4 * block
    return (LANES * b4 * 10 + window // LANES * b4 * 12 * 4
            + 4 * lane_pad(n_out) * 4)


def fem_matvec_fits(window: int, n_out: int, block: int = BLOCK_C) -> bool:
    """The size rule of the matvec dispatch: the kernel's working set at
    this window over ``n_out`` slots fits ``VMEM_BUDGET``."""
    return fem_matvec_vmem_bytes(window, n_out, block) <= VMEM_BUDGET


def fem_matvec_max_verts(block: int = BLOCK_C) -> int:
    """The largest ``n_out`` whose whole-part window (``W = Vp``) fits."""
    n = LANES
    while fem_matvec_fits(n + LANES, n + LANES, block):
        n += LANES
    return n


def fem_matvec_windows(tets: np.ndarray, n_out: int, block: int = BLOCK_C):
    """Host: the window of each element tile of a packing.

    ``tets``: (..., C, 4) slot ids, ``n_out`` and above padding.  Returns
    ``(starts, window, span)``: ``starts`` (..., ceil(C / block)) int32
    first window row of each tile (slots ``128 * start`` on), ``window``
    the static width ``W`` -- the rows the widest tile needs, bucketed to
    a power of two, at most the lane-padded part ``Vp`` -- and ``span``
    the largest tile's extent of real slots (max - min + 1).  A tile with
    no real slot starts at row 0."""
    tets = np.asarray(tets)
    lead, C = tets.shape[:-2], tets.shape[-2]
    vr = lane_pad(n_out) // LANES
    nt = -(-C // block)
    t = np.full(lead + (nt * block, 4), n_out, np.int64)
    t[..., :C, :] = tets
    t = t.reshape(lead + (nt, 4 * block))
    real = t < n_out
    lo = np.where(real, t, n_out).min(axis=-1)
    hi = np.where(real, t, -1).max(axis=-1)
    used = hi >= 0
    span = int((hi - lo + 1)[used].max(initial=0))
    need = int((hi // LANES - lo // LANES + 1)[used].max(initial=1))
    rows = min(1 << (need - 1).bit_length(), vr)
    starts = np.where(used, np.minimum(lo // LANES, vr - rows), 0)
    return starts.astype(np.int32), rows * LANES, span


@functools.partial(jax.jit, static_argnames=("n_out", "block"))
def fem_matvec_tiles(tets: jax.Array, kel: jax.Array, n_out: int,
                     block: int = BLOCK_C):
    """The kernel's operands for a fixed packing, built once: (..., 1,
    4 Cp) int32 slot ids, each tile's four corners one after another, and
    (..., 16, Cp) rows of ``K_e``.  Ragged C is padded with no-op
    elements (slot ``n_out``, ``K_e = 0``)."""
    *lead, C, _ = tets.shape
    pad = (-C) % block
    nt = (C + pad) // block
    width = [(0, 0)] * len(lead) + [(0, pad), (0, 0)]
    t = jnp.pad(tets.astype(jnp.int32), width, constant_values=n_out)
    k = jnp.pad(kel.reshape(*lead, C, 16), width)
    t = jnp.swapaxes(t.reshape(*lead, nt, block, 4), -1, -2)
    return (t.reshape(*lead, 1, nt * 4 * block),
            jnp.swapaxes(k, -1, -2))


@functools.partial(jax.jit,
                   static_argnames=("n_out", "window", "interpret"))
def fem_matvec_window(ids: jax.Array, kel: jax.Array, starts: jax.Array,
                      u: jax.Array, n_out: int, *, window: int,
                      interpret: bool = False) -> jax.Array:
    """Windowed element matvec in one launch.

    ``ids`` / ``kel``: one part's ``fem_matvec_tiles``, (1, 4 Cp) and
    (16, Cp); ``starts``: (Cp / block,) first window row per tile
    (``fem_matvec_windows``); ``u``: (V,) vertex values, V >= n_out.
    Every real slot of a tile must lie in its window
    ``[128 start, 128 start + window)``; the window lies inside
    ``[0, Vp)``.  Returns (n_out,) accumulated contributions."""
    nt = starts.shape[0]
    if nt == 0:
        return jnp.zeros((n_out,), u.dtype)
    block = kel.shape[1] // nt
    vp = lane_pad(n_out)
    vr, rows = vp // LANES, window // LANES
    assert window % LANES == 0 and rows <= vr, (window, vp)
    x = u[:n_out]
    if vp > n_out:
        x = jnp.concatenate([x, jnp.zeros((vp - n_out,), u.dtype)])
    out = pl.pallas_call(
        functools.partial(_window_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nt,),
            in_specs=[pl.BlockSpec((1, 4 * block), lambda i, s: (0, i)),
                      pl.BlockSpec((16, block), lambda i, s: (0, i)),
                      pl.BlockSpec((vr, LANES), lambda i, s: (0, 0))],
            out_specs=pl.BlockSpec((vr, LANES), lambda i, s: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((vr, LANES), u.dtype),
        interpret=interpret,
    )(starts, ids, kel.astype(u.dtype), x.reshape(vr, LANES))
    return out.reshape(vp)[:n_out]


@functools.partial(jax.jit,
                   static_argnames=("n_out", "interpret", "block"))
def fem_matvec_pallas(tets: jax.Array, kel: jax.Array, u: jax.Array,
                      n_out: int, *, interpret: bool = False,
                      block: int = BLOCK_C) -> jax.Array:
    """Fused element matvec over the whole part (``W = Vp``) in one launch.

    ``tets``: (C, 4) int32 slot ids in [0, n_out] (n_out = pad slot,
    dropped); ``kel``: (C, 4, 4) precomputed element matrices
    (`fem_element_matrices`); ``u``: (V,) vertex values with V >= n_out.
    Returns (n_out,) accumulated element contributions.  Arbitrary C:
    element tiles are padded with no-op elements.  ``block`` (elements
    per tile) is a lane multiple."""
    C = tets.shape[0]
    if C == 0:
        return jnp.zeros((n_out,), u.dtype)
    assert block % LANES == 0, block
    block = min(block, lane_pad(C))
    ids, ks = fem_matvec_tiles(tets, kel, n_out, block)
    starts = jnp.zeros((-(-C // block),), jnp.int32)
    return fem_matvec_window(ids, ks, starts, u, n_out,
                             window=lane_pad(n_out), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_out",))
def fem_matvec_jnp(tets: jax.Array, kel: jax.Array, u: jax.Array,
                   n_out: int) -> jax.Array:
    """The kernel's precomputed-K math as fused XLA ops.

    Used by the benchmarks as the CPU-executable stand-in for the
    compiled kernel (interpret mode times the Pallas *emulator*, not
    the op) and by the tests as a second oracle: one gather, one 4x4
    apply against the streamed K_e (no per-call geometry einsums), one
    scatter-add.  Beats the geometry-recomputing oracle on CPU; on TPU
    the Pallas form additionally removes the serialized scatter."""
    nv = u.shape[0]
    ue = gather_corners(u, jnp.minimum(tets, nv - 1))  # (C, 4); pad -> x0
    au = jnp.einsum("cij,cj->ci", kel.astype(u.dtype), ue,
                    precision=_HIGHEST)
    # pad rows have K_e = 0 -> au = 0; out-of-range slots drop
    return jax.ops.segment_sum(au.reshape(-1), tets.reshape(-1),
                               num_segments=n_out)
