"""Compile the main path's Pallas kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present.  That catches what the
interpret-mode parity tests in ``test_kernels.py`` cannot -- block shapes
off the (8, 128) tiling, layouts the kernel compiler refuses, and kernels
that need more VMEM than a core may use -- at real sizes, with no chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.  Keep all such compiles in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fem.parallel import element_apply
from repro.kernels import ops
from repro.kernels.fem_matvec import (BLOCK_C, fem_matvec_fits,
                                      fem_matvec_jnp, fem_matvec_max_verts,
                                      fem_matvec_pallas, fem_matvec_tiles,
                                      fem_matvec_window)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ksection_hist import ksection_histogram_pallas
from repro.kernels.serve_prefill import packed_attention_pallas
from repro.kernels.sfc_keys import sfc_keys_pallas

N_ITEMS = 1 << 20          # one balance step's elements on one chip
N_CUTS = 252               # (p - 1) * k candidate cuts at p = 64, k = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("curve", ["hilbert", "morton"])
def test_sfc_keys_compiles(shape, curve):
    xyz = [shape((N_ITEMS,), jnp.int32)] * 3
    _compile(lambda x, y, z: sfc_keys_pallas(x, y, z, curve=curve), *xyz)


def test_ksection_hist_compiles(shape):
    _compile(ksection_histogram_pallas, shape((N_ITEMS,), jnp.float32),
             shape((N_ITEMS,), jnp.float32), shape((N_CUTS,), jnp.float32))


def test_fem_matvec_compiles_at_largest_kernel_part(shape):
    v = fem_matvec_max_verts()
    c = 4 * v                                     # ~6 tets per vertex
    _compile(lambda t, k, u: fem_matvec_pallas(t, k, u, v),
             shape((c, 4), jnp.int32), shape((c, 4, 4), jnp.float32),
             shape((v + 1,), jnp.float32))


def test_fem_matvec_compiles_windowed_at_cell_size(shape):
    """The kernel the adaptive cell's solve runs: 737,700 elements of one
    chip's part against a resident vector of ~140k slots, each tile in
    a 1,024-slot window."""
    c, v, w = 737_700, 140_000, 1024
    tiles = -(-c // BLOCK_C)
    ids, ks = jax.eval_shape(
        lambda t, k: fem_matvec_tiles(t, k, v),
        shape((c, 4), jnp.int32), shape((c, 4, 4), jnp.float32))
    assert fem_matvec_fits(w, v)
    _compile(lambda i, k, s, u: fem_matvec_window(i, k, s, u, v, window=w),
             shape(ids.shape, ids.dtype), shape(ks.shape, ks.dtype),
             shape((tiles,), jnp.int32), shape((v,), jnp.float32))


def test_fem_matvec_size_rule_picks_twin_for_large_parts(monkeypatch):
    """The dispatch as a TPU applies it: the whole-part kernel up to the
    largest part that fits its VMEM budget, the XLA twin beyond; a
    narrow window keeps a large part on the kernel."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    v = fem_matvec_max_verts()
    assert 1000 < v < 150_000
    assert ops.fem_matvec_path(v) == "window"
    assert ops.fem_matvec_path(v + 1) == "xla"
    assert ops.fem_matvec_path(150_000) == "xla"
    assert ops.fem_matvec_path(150_000, window=1024) == "window"
    assert ops.fem_matvec_path(150_000, window=64 * 1024) == "xla"


@pytest.mark.parametrize("impl", ["twin", "oracle"])
def test_fem_matvec_xla_paths_gather_per_corner(shape, impl):
    """The matvec a large part runs on the chip, at the smoke run's final
    size.  A gather indexed by the (C, 4) connectivity itself made this
    program take minutes to compile and its cost model read ~580 GB per
    call; gathered per corner it reads ~21 GB."""
    c, v = 1_100_000, 200_000
    t, u = shape((c, 4), jnp.int32), shape((v,), jnp.float32)
    if impl == "twin":
        fn = lambda t, k, u: fem_matvec_jnp(t, k, u, v)
        args = (t, shape((c, 4, 4), jnp.float32), u)
    else:
        fn = lambda t, g, vol, u: element_apply(t, g, vol, u, v, 1.0)
        args = (t, shape((c, 4, 3), jnp.float32), shape((c,), jnp.float32),
                u)
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 100e9


def test_packed_attention_compiles(shape):
    q = shape((32, 4096, 128), jnp.bfloat16)
    kv = shape((8, 4096, 128), jnp.bfloat16)
    _compile(packed_attention_pallas, q, kv, kv, shape((4096,), jnp.int32))


def test_flash_attention_compiles(shape):
    q = shape((1, 32, 4096, 128), jnp.bfloat16)
    _compile(flash_attention_pallas, q, q, q)
