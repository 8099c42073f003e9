"""Pallas TPU kernel: fused RMSNorm (read once, normalize + scale in VMEM).

Row-tiled: each grid step normalizes a (block_rows x d) tile; the mean of
squares accumulates in f32 regardless of the input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6,
                   block_rows: int = 8, *, interpret: bool):
    """x: (..., d); scale: (d,).  ``interpret``: Pallas interpreter (CPU)
    or Mosaic lowering (TPU)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = int(x.size // d)
    x2 = x.reshape(rows, d)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=((rows + pad) // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, scale)
    return out[:rows].reshape(orig_shape)
