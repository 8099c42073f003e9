"""Compile rehearsal: every Pallas kernel lowers through Mosaic for a TPU v5e.

Nothing runs here.  Each test compiles one raw kernel with
``interpret=False`` for a described (not attached) ``v5e:2x2`` chip at the
widths the planning service uses (paper cells: N=50 users, M=5 edges,
128 cells), and asserts that the compiled program holds the kernel, by
name, as a ``tpu_custom_call``.  It catches what interpret mode cannot:
block shapes Mosaic refuses, unaligned slices, over-budget VMEM, removed
Pallas APIs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (flash_attention, ops, rmsnorm, sroa_bisect,
                           topk_moves)

CELLS, N, M, TOP_K = 128, 50, 5, 8
CANDIDATES = 1 + N * (M - 1)          # the engine's full neighbourhood


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compilation_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernels(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return ops.compiled_kernel_names(text)


def test_sroa_solve_compiles_for_v5e(one_chip):
    P = CELLS * CANDIDATES
    u = jax.ShapeDtypeStruct((P, N), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((P,), jnp.float32, sharding=one_chip)

    def fn(*args):
        return sroa_bisect.sroa_solve_pallas(*args, b_iters=30, f_iters=24,
                                             p_iters=20, t_iters=28,
                                             interpret=False)

    assert _kernels(fn, *([u] * 7 + [s] * 5)) == {"sroa_solve"}


def test_sroa_bisect_vec_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((CELLS * N,), jnp.float32, sharding=one_chip)

    def fn(G, tgt, bm):
        return sroa_bisect.sroa_bisect_pallas_vec(G, tgt, bm, iters=30,
                                                  interpret=False)

    assert _kernels(fn, x, x, x) == {"sroa_bisect_vec"}


def test_topk_moves_compiles_for_v5e(one_chip):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(gain, H, p_max, assign, mask, N0, B):
        return topk_moves.topk_moves_pallas(gain, H, p_max, assign, mask, N0,
                                            B, k=TOP_K, interpret=False)

    names = _kernels(fn, sds((CELLS, N, M)), sds((CELLS, N)),
                     sds((CELLS, N)), sds((CELLS, N), jnp.int32),
                     sds((CELLS, N), jnp.bool_), sds((CELLS,)),
                     sds((CELLS,)))
    assert names == {"topk_moves"}


def test_rmsnorm_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((4, 512, 2048), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((2048,), jnp.bfloat16, sharding=one_chip)

    def fn(x, s):
        return rmsnorm.rmsnorm_pallas(x, s, interpret=False)

    assert _kernels(fn, x, s) == {"rmsnorm"}


def test_flash_attention_compiles_for_v5e(one_chip):
    q = jax.ShapeDtypeStruct((1, 8, 512, 128), jnp.bfloat16,
                             sharding=one_chip)

    def fn(q, k, v):
        return flash_attention.flash_attention_pallas(q, k, v, causal=True,
                                                      interpret=False)

    assert _kernels(fn, q, q, q) == {"flash_attention"}
