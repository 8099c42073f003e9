"""Pallas top-k move pruning for the assignment engine (DESIGN.md D9).

The engine's full neighbourhood is ``A = 1 + N*(M-1)`` candidate patterns
per round, each scored with a complete constants-space SROA — quadratic
work per round once candidate count and per-candidate cost both grow with
N.  This kernel computes a CHEAP marginal-cost estimate for every
(user, target-edge) move — no bisections, just the airtime each move adds
or removes — and emits the indices of the k most promising moves, so only
k+1 candidates reach the full SROA scoring path.

Score model (one segmented reduction + element-wise work): a user's
airtime demand on edge m is ``a(n, m) = H_n / se(n, m)`` with
``se = log2(1 + gain*p_max/(N0*b_ref))`` the spectral efficiency at the
equal-split reference bandwidth ``b_ref = B / n_active``.  The move
n: s -> m is scored by the airtime delta weighted by post-move edge
occupancy (the segmented load term):

    score(n, m) = a(n, m) * (1 + (c_m + 1)/n_act)
                - a(n, s) * (1 + c_s     /n_act)

where ``c_m`` counts active users on edge m under the CURRENT pattern.
Negative score = predicted improvement; the k smallest scores win.  Own
edges, inactive users and padded rows/columns are scored ``+BIG`` so they
never enter the top-k.  This is an estimate, not the objective — the
approximation contract (how pruning composes with multi-start restarts)
is recorded in DESIGN.md D9 and guarded by tests/test_engine.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
_BIG = 1e30
_LN2 = 0.6931471805599453


def _topk_kernel(g_ref, h_ref, pm_ref, as_ref, mk_ref, scal_ref,
                 idx_ref, val_ref, *, k: int, M: int):
    g = g_ref[0]                              # (Np, Mp) gain
    H = h_ref[0]                              # (Np, 1) upload bits
    pm = pm_ref[0]                            # (Np, 1) max power
    an = as_ref[0]                            # (Np, 1) current edge (i32)
    mk = mk_ref[0]                            # (Np, 1) active mask (f32)
    scal = scal_ref[0]                        # (1, 8)
    N0 = scal[:, 0:1]                         # (1, 1)
    b_ref = scal[:, 1:2]

    shape = g.shape
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)

    # Airtime demand a(n, m) at the equal-split reference bandwidth.
    snr = g * pm / jnp.maximum(N0 * b_ref, 1e-30)
    se = jnp.log1p(snr) / _LN2
    a = H / jnp.maximum(se, 1e-9)

    # Segmented reduction: active-user count per edge (current pattern).
    cur = (col == an).astype(jnp.float32) * mk        # (Np, Mp) one-hot
    c_m = jnp.sum(cur, axis=0, keepdims=True)         # (1, Mp) loads
    n_act = jnp.maximum(jnp.sum(mk), 1.0)
    a_src = jnp.sum(a * cur, axis=1, keepdims=True)   # (Np, 1) a(n, s)
    c_src = jnp.sum(c_m * cur, axis=1, keepdims=True)  # (Np, 1) load of s

    score = (a * (1.0 + (c_m + 1.0) / n_act)
             - a_src * (1.0 + c_src / n_act))
    valid = (col < M) & (mk > 0) & (col != an)
    score = jnp.where(valid, score, _BIG)

    # Iterative top-k: k rounds of (global argmin, record, knock out).
    Mp = shape[1]
    flat = row * Mp + col
    Kp = idx_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Kp), 1)

    def body(i, carry):
        sc, idxv, valv = carry
        mn = jnp.min(sc)
        pos = jnp.min(jnp.where(sc == mn, flat, jnp.int32(2 ** 30)))
        idxv = jnp.where(lane == i, pos, idxv)
        valv = jnp.where(lane == i, mn, valv)
        sc = jnp.where(flat == pos, _BIG, sc)
        return sc, idxv, valv

    idx0 = jnp.zeros((1, Kp), jnp.int32)
    val0 = jnp.full((1, Kp), _BIG, jnp.float32)
    _, idxv, valv = jax.lax.fori_loop(0, k, body, (score, idx0, val0))
    idx_ref[0] = idxv
    val_ref[0] = valv


def topk_moves_pallas(gain, H, p_max, assign, mask, N0, B, *, k: int,
                      interpret: bool):
    """Top-k single-user moves for P independent cells in one launch.

    Args:
      gain:   (P, N, M) f32 user->edge channel gains.
      H:      (P, N) f32 upload bits (any common positive scale).
      p_max:  (P, N) f32 per-user max transmit power.
      assign: (P, N) i32 current pattern.
      mask:   (P, N) bool active users.
      N0, B:  (P,) f32 noise PSD and cell bandwidth budget.
      k:      static number of moves to keep.
      interpret: run the kernel body in the Pallas interpreter (CPU) or
              lower it through Mosaic (TPU).
    Returns:
      (user, dst, score): each (P, k); rows with ``score >= _BIG/2`` are
      padding (fewer than k valid moves existed).
    """
    gain = jnp.asarray(gain, jnp.float32)
    P, N, M = gain.shape
    n_pad = (-N) % LANES
    m_pad = (-M) % LANES
    Np, Mp = N + n_pad, M + m_pad
    Kp = max(LANES, ((k + LANES - 1) // LANES) * LANES)

    gp = jnp.pad(gain, ((0, 0), (0, n_pad), (0, m_pad)),
                 constant_values=1e-12)

    # Per-user operands travel as (P, Np, 1) columns and per-problem ones
    # as (P, 1, 8) rows: each grid step's block then spans the full last
    # two dimensions, which is what Mosaic requires of a one-problem block.
    def pad_u(x, dtype, fill):
        x = jnp.asarray(x, dtype)
        x = jnp.pad(x, ((0, 0), (0, n_pad)), constant_values=fill)
        return x[..., None]

    Hp = pad_u(H, jnp.float32, 0.0)
    pmp = pad_u(p_max, jnp.float32, 1.0)
    asp = pad_u(assign, jnp.int32, 0)
    mkp = pad_u(mask, jnp.float32, 0.0)

    n_act = jnp.maximum(jnp.sum(jnp.asarray(mask, jnp.float32), axis=1),
                        1.0)
    b_ref = jnp.asarray(B, jnp.float32) / n_act
    scal = jnp.stack([jnp.broadcast_to(jnp.asarray(N0, jnp.float32), (P,)),
                      b_ref] + [jnp.zeros((P,), jnp.float32)] * 6,
                     axis=1)[:, None, :]

    gspec = pl.BlockSpec((1, Np, Mp), lambda i: (i, 0, 0))
    uspec = pl.BlockSpec((1, Np, 1), lambda i: (i, 0, 0))
    sspec = pl.BlockSpec((1, 1, 8), lambda i: (i, 0, 0))
    kspec = pl.BlockSpec((1, 1, Kp), lambda i: (i, 0, 0))
    idx, val = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, M=M),
        grid=(P,),
        in_specs=[gspec, uspec, uspec, uspec, uspec, sspec],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((P, 1, Kp), jnp.int32),
                   jax.ShapeDtypeStruct((P, 1, Kp), jnp.float32)],
        interpret=interpret,
        name="topk_moves",
    )(gp, Hp, pmp, asp, mkp, scal)
    idx, val = idx[:, 0, :k], val[:, 0, :k]
    return idx // Mp, idx % Mp, val
