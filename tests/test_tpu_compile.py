"""Compile rehearsal: every Pallas kernel lowers through Mosaic for a TPU v5e.

Nothing runs here.  Each kernel test compiles one raw kernel with
``interpret=False`` for a described (not attached) ``v5e:2x2`` chip at the
widths the planning service uses (paper cells: N=50 users, M=5 edges,
128 cells), and asserts that the compiled program holds the kernel, by
name, as a ``tpu_custom_call``.  It catches what interpret mode cannot:
block shapes Mosaic refuses, unaligned slices, over-budget VMEM, removed
Pallas APIs.  One more compiles the jnp route's Algorithm 2 at the engine's
scoring shape and checks the structure of the program XLA makes of it.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sroa
from repro.core.system_model import SroaConstants
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


def _computations(text):
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) ", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith(" "):
            comps[name].append(line)
    return comps


def _count_in(comps, name, op):
    """``op`` instructions in computation ``name`` and the fusions it calls."""
    n = 0
    for line in comps[name]:
        n += len(re.findall(rf"\b{op}\(", line))
        if " fusion(" in line:
            n += _count_in(comps, re.search(r"calls=%([\w.-]+)", line)
                           .group(1), op)
    return n


def test_algorithm2_inversion_fuses_for_v5e(one_chip):
    """The engine's scoring of one re-search bucket (16 cells x the full
    neighbourhood x N users, served caps): each b inversion is one device
    loop body holding every bisection step, not a loop of one launch per
    step."""
    C = 16
    cfg = sroa.SroaConfig(b_iters=30, f_iters=24)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    u = sds(C, CANDIDATES, N)
    consts = SroaConstants(A=u, J=u, H=u, delta=u, h=u,
                           E_cloud_total=sds(C, CANDIDATES))

    def fn(consts, p, t, B, f_max, N0):
        def alg2(c, p, t, B, f_max, N0):
            return sroa.algorithm2(c, p, t, B, B, f_max, N0, cfg)
        inner = jax.vmap(alg2, in_axes=(0, 0, 0, None, None, None))
        return jax.vmap(inner)(consts, p, t, B, f_max, N0)

    text = jax.jit(fn).lower(consts, u, sds(C, CANDIDATES), sds(C),
                             sds(C, N), sds(C)).compile().as_text()
    comps = _computations(text)
    bodies = re.findall(r"\bwhile\(.*?body=%([\w.-]+)", text)
    # The f bisection, the inversion inside its step, the final inversion.
    assert len(bodies) == 3
    inner = [b for b in bodies if _count_in(comps, b, "while") == 0]
    assert len(inner) == 2
    for body in inner:     # one log1p per bisection step
        assert _count_in(comps, body, "log-plus-one") >= cfg.b_iters


@pytest.mark.parametrize("batch,carry,flattens", [
    ((16, CANDIDATES), "1264,128", 1),  # 16 x 201 x 50 in 158 whole tiles
    ((16,), "16,50", 0),                # the re-pricing keeps its layout
], ids=["engine", "reprice"])
def test_algorithm2_inversion_lane_dense_for_v5e(one_chip, batch, carry,
                                                 flattens):
    """The engine's scoring of a 16-row bucket runs each inversion loop on
    a lane-dense (rows, 128) stream of the whole batch, and each step of
    the f search streams only its target in (G and b_max stream once per
    search); the re-pricing's one vmap over 16 cells keeps the (cells, N)
    tiles.  Either way each problem's b_sum reduces its users' axis of
    the (..., N) layout."""
    cfg = sroa.SroaConfig(b_iters=30, f_iters=24)
    C = batch[0]

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    u = sds(*batch, N)
    consts = SroaConstants(A=u, J=u, H=u, delta=u, h=u,
                           E_cloud_total=sds(*batch))

    def fn(consts, p, t, B, f_max, N0):
        def alg2(c, p, t, B, f_max, N0):
            return sroa.algorithm2(c, p, t, B, B, f_max, N0, cfg)
        for _ in batch[1:]:
            alg2 = jax.vmap(alg2, in_axes=(0, 0, 0, None, None, None))
        return jax.vmap(alg2)(consts, p, t, B, f_max, N0)

    text = jax.jit(fn).lower(consts, u, sds(*batch), sds(C), sds(C, N),
                             sds(C)).compile().as_text()
    # The inversion's loops carry (flag, lo/hi bound, G, target).
    carries = [m.group(1) for line in text.splitlines() if " while(" in line
               for m in [re.search(r"= \(pred\[\][^,]*, f32\[([\d,]+)\]",
                                   line)] if m]
    assert carries == [carry, carry]
    # b_sum is still summed over each problem's N users, in their layout.
    sums = re.findall(r"= f32\[([\d,]+)\]\S* reduce\(.*?dimensions="
                      r"\{(\d+)\}.*?reduce_sum", text)
    assert set(sums) == {(",".join(map(str, batch)), str(len(batch)))}
    comps = _computations(text)
    f_step = [b for b in re.findall(r"\bwhile\(.*?body=%([\w.-]+)", text)
              if _count_in(comps, b, "while")]
    # What the f step flattens into the stream: its target alone.
    flat = rf"= f32\[{N * math.prod(batch)}\]\S* reshape\("
    assert len(f_step) == 1
    assert sum(bool(re.search(flat, line))
               for line in comps[f_step[0]]) == flattens


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
