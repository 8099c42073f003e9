"""Pallas TPU kernel: blockwise (flash) attention forward.

Online-softmax attention with explicit VMEM tiling: the (Tq x Tk) score
matrix never exists; each (block_q x block_k) tile is produced in VMEM,
folded into running (max, denom, acc) statistics, and discarded.  Designed
for the MXU: block shapes are multiples of 128 and the two matmuls per tile
((bq,hd)x(hd,bk) and (bq,bk)x(bk,hd)) are MXU-shaped.

Supports causal masking with a query offset (decode) and sliding windows
(zamba2's shared attention).  Head dim is padded to 128 lanes by the ops.py
wrapper.  Validated against ref.py in interpret mode on every shape/dtype in
tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
                  seq_k: int, causal: bool, q_offset: int, window,
                  scale: float):
    qi = pl.program_id(1)                      # query block index
    q = q_ref[0].astype(jnp.float32) * scale   # (block_q, hd)

    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    denom = jnp.zeros((block_q,), jnp.float32)

    q_pos = q_offset + qi * block_q + jax.lax.iota(jnp.int32, block_q)

    def body(kb, carry):
        acc, m, denom = carry
        rows = pl.ds(kb * block_k, block_k)
        k = k_ref[0, rows, :].astype(jnp.float32)
        v = v_ref[0, rows, :].astype(jnp.float32)
        s = q @ k.T                            # (block_q, block_k)
        k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((block_q, block_k), bool)
        mask &= (k_pos < seq_k)[None, :]
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > (q_pos[:, None] - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        denom = denom * alpha + p.sum(-1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_new, denom

    n_kb = (seq_k + block_k - 1) // block_k
    if causal:
        # only key blocks at or before this query block contribute
        last = jnp.minimum(
            n_kb, (q_offset + (qi + 1) * block_q + block_k - 1) // block_k)
    else:
        last = n_kb
    acc, m, denom = jax.lax.fori_loop(0, last, body, (acc, m, denom))
    o_ref[0] = (acc / jnp.maximum(denom, 1e-30)[:, None]).astype(
        o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, q_offset=0, window=None,
                           block_q=128, block_k=128, interpret: bool):
    """q: (B, H, Tq, hd), k/v: (B, H, Tk, hd) with hd a multiple of 128.

    ``interpret`` runs the kernel body in the Pallas interpreter (CPU) or
    lowers it through Mosaic (TPU); :mod:`repro.kernels.ops` picks it from
    the backend.

    Returns (B, H, Tq, hd) in q.dtype.
    """
    B, H, Tq, hd = q.shape
    Tk = k.shape[2]
    scale = 1.0 / np.sqrt(hd)
    block_q = min(block_q, max(Tq, 8))
    block_k = min(block_k, max(Tk, 8))
    q_pad = (-Tq) % block_q
    k_pad = (-Tk) % block_k
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    Tq_p, Tk_p = Tq + q_pad, Tk + k_pad

    qf = q.reshape(B * H, Tq_p, hd)
    kf = k.reshape(B * H, Tk_p, hd)
    vf = v.reshape(B * H, Tk_p, hd)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_q=block_q, block_k=block_k, seq_k=Tk,
            causal=causal, q_offset=q_offset, window=window, scale=scale),
        grid=(B * H, Tq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Tk_p, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Tk_p, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq_p, hd), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, H, Tq_p, hd)[:, :, :Tq, :]
