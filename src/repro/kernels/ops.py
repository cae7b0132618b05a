"""jit'd public wrappers for the Pallas kernels.

Each op dispatches between the Pallas kernel and its XLA forms, deciding
the platform when it is called (never at import):

* ``use_pallas=None`` (the default) picks the kernel on a TPU and the
  oracle elsewhere; ``False`` always runs the oracle.
* A kernel runs compiled on a TPU.  Off the TPU it runs under the Pallas
  interpreter only when the caller passes ``interpret=True`` (tests);
  otherwise the op runs the kernel's XLA twin (or the oracle where the
  twin is the oracle), so nothing is interpreted without being asked.

Library code calls these wrappers, never the kernels directly.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref as _ref
from .fem_matvec import (BLOCK_C, fem_element_matrices, fem_matvec_fits,
                         fem_matvec_jnp, fem_matvec_pallas, lane_pad)
from .flash_attention import flash_attention_pallas
from .ksection_hist import (BLOCK_N, ksection_histogram_jnp,
                            ksection_histogram_pallas)
from .prefix_scan import exclusive_scan_pallas
from .serve_prefill import packed_attention_jnp, packed_attention_pallas
from .sfc_keys import sfc_keys_pallas


def on_tpu() -> bool:
    """Whether the default backend is a TPU (asked at call time)."""
    return jax.default_backend() == "tpu"


def _run_kernel(use_pallas: Optional[bool], interpret: bool) -> bool:
    """Resolve the dispatch: True runs the Pallas kernel (compiled on a
    TPU, interpreted when asked), False the op's XLA form."""
    if use_pallas is None:
        use_pallas = on_tpu()
    return bool(use_pallas) and (interpret or on_tpu())


def _pad_to(x: jax.Array, mult: int):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, n


def sfc_keys_op(grid: jax.Array, *, curve: str = "hilbert", bits: int = 10,
                use_pallas: Optional[bool] = None,
                interpret: bool = False) -> jax.Array:
    """(n, 3) integer grid coords -> (n,) keys.  Any n runs the kernel
    (it pads to whole tiles and slices the keys back)."""
    if not _run_kernel(use_pallas, interpret):
        fn = _ref.hilbert_keys_ref if curve == "hilbert" else _ref.morton_keys_ref
        return fn(grid.astype(jnp.uint32), bits)
    g = grid.astype(jnp.int32)
    keys = sfc_keys_pallas(g[:, 0], g[:, 1], g[:, 2], curve=curve,
                           bits=bits, interpret=interpret)
    return keys.astype(jnp.uint32)


def exclusive_scan_op(x: jax.Array, *, use_pallas: Optional[bool] = None,
                      interpret: bool = False) -> jax.Array:
    """Exclusive prefix sum (Algorithm 1 S_i) over (n,)."""
    if not _run_kernel(use_pallas, interpret):
        return _ref.exclusive_scan_ref(x)
    xp, n = _pad_to(x.astype(jnp.float32), 2048)
    return exclusive_scan_pallas(xp, interpret=interpret)[:n]


def ksection_histogram_op(keys: jax.Array, weights: jax.Array,
                          cuts: jax.Array, *,
                          use_pallas: Optional[bool] = None,
                          interpret: bool = False,
                          block: int = BLOCK_N) -> jax.Array:
    """Per-round k-section histogram: weight strictly below each of the
    (m,) candidate cuts (any order).  (n,),(n,),(m,) -> (m,) float32.

    The fused kernel replaces searchsorted + an (m+1)-segment
    segment_sum + cumsum with one streaming compare-accumulate launch.
    ``use_pallas=True`` off the TPU runs its fused-XLA twin
    ``ksection_histogram_jnp`` (the kernel itself with ``interpret=True``).
    Exact on integer-valued weights every way, so the k-section search
    stays bit-identical across implementations."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return _ref.ksection_histogram_ref(keys, weights, cuts)
    if not (interpret or on_tpu()):
        return ksection_histogram_jnp(keys, weights, cuts)
    return ksection_histogram_pallas(keys, weights, cuts,
                                     interpret=interpret, block=block)


def fem_matvec_path(n_out: int, *, window: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False, block: int = BLOCK_C) -> str:
    """Which implementation runs an element matvec over ``n_out`` slots
    whose element tiles need ``window`` slots each (default: the whole
    lane-padded part): ``"window"`` (the kernel: compiled on a TPU,
    interpreted when asked), ``"xla"`` (the gather/scatter twin
    ``fem_matvec_jnp``) or ``"oracle"`` (the geometry-recomputing
    reference).

    The size rule lives here: on a TPU the kernel runs only where its
    window and resident vertex blocks fit its VMEM budget
    (``fem_matvec_fits``); otherwise the twin runs on the device.
    Decided from shapes, never by catching a compile error."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return "oracle"
    if window is None:
        window = lane_pad(n_out)
    if interpret or (on_tpu() and fem_matvec_fits(window, n_out, block)):
        return "window"
    return "xla"


def fem_matvec_op(tets: jax.Array, grads: jax.Array, vol: jax.Array,
                  u: jax.Array, n_out: int, *, c: float = 0.0,
                  kel: Optional[jax.Array] = None,
                  use_pallas: Optional[bool] = None,
                  interpret: bool = False,
                  block: int = BLOCK_C) -> jax.Array:
    """Fused P1 element matvec: (C, 4) slot ids + element geometry against
    a (V,) vertex vector -> (n_out,) accumulated contributions.

    ``use_pallas=False`` (the CPU default) runs the geometry oracle --
    bit-identical to the inline einsum pass in ``fem.parallel``.  The
    kernel path streams precomputed 4x4 element matrices (``kel``; built
    here from (grads, vol, c) when not supplied -- callers on a fixed
    packing should precompute via ``fem_element_matrices`` and pass it)
    through the implementation ``fem_matvec_path`` picks: the compiled
    kernel over the whole part (``W = Vp``) on a TPU where that fits its
    VMEM budget, the Pallas interpreter when ``interpret=True``, and
    otherwise the kernel's fused-XLA twin ``fem_matvec_jnp``.
    Kernel/twin vs oracle differ in accumulation order: tolerance-exact,
    not bit-exact."""
    path = fem_matvec_path(n_out, use_pallas=use_pallas,
                           interpret=interpret, block=block)
    if path == "oracle":
        return _ref.fem_matvec_ref(tets, grads, vol, u, n_out, c=c)
    if kel is None:
        kel = fem_element_matrices(grads, vol, c)
    if path == "window":
        return fem_matvec_pallas(tets, kel, u, n_out, interpret=interpret,
                                 block=block)
    return fem_matvec_jnp(tets, kel, u, n_out)


def packed_attention_op(q: jax.Array, k: jax.Array, v: jax.Array,
                        seg: jax.Array, *, softcap: Optional[float] = None,
                        scale: Optional[float] = None,
                        use_pallas: Optional[bool] = None,
                        interpret: bool = False,
                        block: int = 128) -> jax.Array:
    """Segment-masked causal attention over one packed prefill buffer.

    q: (hq, C, d); k/v: (hkv, C, d) unexpanded (GQA folded per-impl);
    seg: (C,) int32 request ids, -1 = pad.  Rows with no visible key
    emit exactly 0 across all three implementations, so the serving
    engine's parity bar (packed bit-identical on output tokens to
    per-request prefill) holds regardless of backend.  Dispatch follows
    ``fem_matvec_op``: ``use_pallas=False`` (the CPU default) runs the
    oracle; the Pallas kernel runs compiled on TPU or under the
    interpreter with ``interpret=True``; otherwise the fused-XLA twin
    ``packed_attention_jnp`` serves off-TPU production use."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return _ref.packed_attention_ref(q, k, v, seg, softcap=softcap,
                                         scale=scale)
    if interpret or on_tpu():
        return packed_attention_pallas(q, k, v, seg, softcap=softcap,
                                       scale=scale, block=block,
                                       interpret=interpret)
    return packed_attention_jnp(q, k, v, seg, softcap=softcap, scale=scale)


def flash_attention_op(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: Optional[int] = None,
                       use_pallas: Optional[bool] = None,
                       interpret: bool = False) -> jax.Array:
    """Blocked attention; the jnp reference off-TPU unless interpreted."""
    if not _run_kernel(use_pallas, interpret):
        return _ref.mha_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  interpret=interpret)
