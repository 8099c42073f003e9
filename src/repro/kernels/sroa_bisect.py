"""Pallas TPU kernel: batched SROA bandwidth bisection (the paper hotspot).

Inverts the monotone rate function h(b) = b*log2(1 + G/b) >= target for a
block of users entirely in VMEM/registers.  This inner inversion dominates
the paper's complexity analysis (§IV-C: executed O(N * log(1/e0) * log(1/e1)
* log(1/e2)) times inside Algorithms 2-4), and at fleet scale (planning for
10^5-10^6 clients) it is the compute-bound core of the planner.

TPU mapping: pure VPU element-wise work; users are tiled (ROWS x 128) so a
block fills the vector lanes; the bisection loop runs in registers with no
HBM traffic between iterations (one load, `iters` fori steps, one store).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LN2 = float(np.log(2.0))
LANES = 128
ROWS = 8                     # sublane tile: (8, 128) float32


def _rate(b, G):
    b_safe = jnp.maximum(b, 1e-12)
    return b_safe * jnp.log1p(G / b_safe) / LN2


def _bisect_kernel(g_ref, t_ref, b_ref, o_ref, *, iters: int):
    G = g_ref[...]
    tgt = t_ref[...]
    b_max = b_ref[0, 0]
    lo = jnp.zeros_like(G)
    hi = jnp.full_like(G, b_max)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        ok = _rate(mid, G) >= tgt
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    feas = _rate(jnp.full_like(G, b_max), G) >= tgt
    o_ref[...] = jnp.where(feas, hi, b_max)


def sroa_bisect_pallas(G: jnp.ndarray, target: jnp.ndarray, b_max,
                       iters: int = 42, *, block_rows: int = ROWS,
                       interpret: bool) -> jnp.ndarray:
    """G, target: (N,) float32 -> smallest b with rate(b) >= target.

    Pads N up to a (block_rows x 128) tile multiple; grid over row blocks.
    b_max may be a traced scalar (it is the scenario's bandwidth budget).
    """
    N = G.shape[0]
    tile = block_rows * LANES
    n_pad = (-N) % tile
    Gp = jnp.pad(G.astype(jnp.float32), (0, n_pad), constant_values=1.0)
    Tp = jnp.pad(target.astype(jnp.float32), (0, n_pad),
                 constant_values=0.0)
    rows = (N + n_pad) // LANES
    G2 = Gp.reshape(rows, LANES)
    T2 = Tp.reshape(rows, LANES)
    bm = jnp.asarray(b_max, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_bisect_kernel, iters=iters),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
        name="sroa_bisect",
    )(G2, T2, bm)
    return out.reshape(-1)[:N]


def _bisect_kernel_vec(g_ref, t_ref, b_ref, o_ref, *, iters: int):
    """Per-element b_max variant: all three operands are full VPU blocks."""
    G = g_ref[...]
    tgt = t_ref[...]
    bm = b_ref[...]
    lo = jnp.zeros_like(G)
    hi = bm

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        ok = _rate(mid, G) >= tgt
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    feas = _rate(bm, G) >= tgt
    o_ref[...] = jnp.where(feas, hi, bm)


def sroa_bisect_pallas_vec(G: jnp.ndarray, target: jnp.ndarray,
                           b_max: jnp.ndarray, iters: int = 42, *,
                           block_rows: int = ROWS,
                           interpret: bool) -> jnp.ndarray:
    """Fleet-batched inversion: per-element bandwidth caps.

    G, target, b_max: (N,) float32 where N is typically a flattened
    batch x users axis — a fleet of scenarios (each with its own budget,
    hence the vector b_max) packed so one call fills whole (8 x 128)
    tiles instead of padding each small cell up to a tile on its own.
    """
    N = G.shape[0]
    tile = block_rows * LANES
    n_pad = (-N) % tile
    Gp = jnp.pad(G.astype(jnp.float32), (0, n_pad), constant_values=1.0)
    Tp = jnp.pad(target.astype(jnp.float32), (0, n_pad),
                 constant_values=0.0)
    Bp = jnp.pad(b_max.astype(jnp.float32), (0, n_pad),
                 constant_values=1.0)
    rows = (N + n_pad) // LANES
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))

    out = pl.pallas_call(
        functools.partial(_bisect_kernel_vec, iters=iters),
        grid=(rows // block_rows,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
        name="sroa_bisect_vec",
    )(Gp.reshape(rows, LANES), Tp.reshape(rows, LANES),
      Bp.reshape(rows, LANES))
    return out.reshape(-1)[:N]


# ===========================================================================
# Fused constants-space SROA solve: ALL THREE nested bisections in one kernel
# ===========================================================================
#
# ``sroa_solve_pallas`` runs the paper's Algorithms 2-4 end to end — the
# `_auto_bounds` deadline bracketing, the value-guided bisection on t, the
# power bisection (Alg 3), the lockstep frequency bisection (Alg 2) and the
# innermost bandwidth inversion (Lemma 1) — for a BLOCK of independent
# problems without ever leaving the kernel.  This is the candidate-scoring
# hot loop of the assignment engine: under the engine's double vmap
# (candidates x cells) the pure-JAX path bounces through four levels of XLA
# `while_loop` per candidate; here the whole trajectory is register/VMEM
# resident and one launch scores every flattened candidate.
#
# Layout: problems in sublanes, users in lanes — a block is
# (BLOCK_P, N_pad) with N_pad a lane-tile multiple, so per-problem scalars
# (deadline brackets, objective) are (BLOCK_P, 1) columns and per-user state
# (b, f, p intervals) fills the vector lanes.  Early stopping is mirrored
# from the jnp path by freezing converged problems inside fixed-trip
# `fori_loop`s (`jnp.where(active, new, old)`), which keeps trajectories
# identical to `lax.while_loop` with the same tolerances.
#
# Padded users are neutralized exactly like
# :func:`repro.core.system_model.mask_constants` (A = J = H = delta = 0,
# h = 1) so they follow the same t-grid as an unpadded solve; padded
# problems solve a harmless all-masked instance whose rows are dropped.

BLOCK_P = 8                  # problems per block (sublane tile)


def _solve_kernel(a_ref, j_ref, h_ref, d_ref, g_ref, fm_ref, pm_ref,
                  scal_ref, b_ref, f_ref, p_ref, s_ref, *,
                  b_iters: int, f_iters: int, p_iters: int, t_iters: int,
                  eps0: float, eps1: float, eps2: float,
                  t_low: float, t_up: float):
    big = 1e30
    A_ = a_ref[...]                      # (BP, N) compute-energy constant
    Jc = j_ref[...]                      # (BP, N) compute-load constant
    Hc = h_ref[...]                      # (BP, N) upload bits
    dl = d_ref[...]                      # (BP, N) cloud-delay offset
    hg = g_ref[...]                      # (BP, N) channel gain
    fmax = fm_ref[...]                   # (BP, N)
    pmax = pm_ref[...]                   # (BP, N)
    scal = scal_ref[...]                 # (BP, 8)
    B = scal[:, 0:1]
    bmax = scal[:, 1:2]
    N0 = scal[:, 2:3]
    lam = scal[:, 3:4]
    ect = scal[:, 4:5]                   # E_cloud_total

    def inv(G, tgt, bm):
        """invert_rate: smallest b with rate(b) >= tgt (bm broadcasts)."""
        bmb = jnp.broadcast_to(bm, G.shape)
        feas = _rate(bmb, G) >= tgt

        def body(_, lohi):
            lo, hi = lohi
            mid = 0.5 * (lo + hi)
            ok = _rate(mid, G) >= tgt
            return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

        lo, hi = jax.lax.fori_loop(0, b_iters, body,
                                   (jnp.zeros_like(G), bmb))
        return jnp.where(feas, hi, bmb)

    def alg2(p, t):
        """Lockstep f bisection + inner b inversion (paper Alg 2)."""
        G = p * hg / N0
        denom = t - dl - LN2 * Hc / jnp.maximum(G, 1e-30)
        f_lo0 = jnp.where(denom > 0, Jc / jnp.maximum(denom, 1e-30), fmax)
        f_lo0 = jnp.clip(f_lo0, 0.0, fmax)

        def b_of_f(f):
            tau = t - dl - Jc / jnp.maximum(f, 1.0)
            tgt = jnp.where(tau > 0, Hc / jnp.maximum(tau, 1e-30), big)
            return inv(G, tgt, bmax)

        def body(_, lohi):
            f_lo, f_hi = lohi
            gap = jnp.max((f_hi - f_lo) / jnp.maximum(f_hi, 1.0),
                          axis=1, keepdims=True)
            act = gap > eps0
            f = 0.5 * (f_lo + f_hi)
            b_sum = jnp.sum(b_of_f(f), axis=1, keepdims=True)
            spare = b_sum < B
            nlo = jnp.where(spare, f_lo, f)
            nhi = jnp.where(spare, f, f_hi)
            return (jnp.where(act, nlo, f_lo), jnp.where(act, nhi, f_hi))

        _, f_hi = jax.lax.fori_loop(0, f_iters, body, (f_lo0, fmax))
        b = b_of_f(f_hi)
        return b, f_hi, jnp.sum(b, axis=1, keepdims=True)

    def alg3(t):
        """p bisection (paper Alg 3), Lemma-2 lower bound."""
        gamma = Hc / bmax
        eta = t - dl - Jc / fmax
        zeta = N0 * bmax / hg
        expo = jnp.clip(gamma / jnp.maximum(eta, 1e-30), 0.0, 60.0)
        p_lo0 = jnp.where(eta > 0, zeta * (2.0 ** expo - 1.0), pmax)
        p_lo0 = jnp.clip(p_lo0, 0.0, pmax)

        def body(_, lohi):
            p_lo, p_hi = lohi
            gap = jnp.max((p_hi - p_lo) / jnp.maximum(p_hi, 1e-12),
                          axis=1, keepdims=True)
            act = gap > eps1
            p = 0.5 * (p_lo + p_hi)
            _, _, b_sum = alg2(p, t)
            spare = b_sum < B
            nlo = jnp.where(spare, p_lo, p)
            nhi = jnp.where(spare, p, p_hi)
            return (jnp.where(act, nlo, p_lo), jnp.where(act, nhi, p_hi))

        _, p_hi = jax.lax.fori_loop(0, p_iters, body, (p_lo0, pmax))
        b, f, b_sum = alg2(p_hi, t)
        return b, f, p_hi, b_sum

    def energy(b, f, p):
        G = p * hg / N0
        T_com = jnp.where(b > 0, Hc / jnp.maximum(_rate(b, G), 1e-30), big)
        E = jnp.sum(p * T_com + A_ * f ** 2, axis=1, keepdims=True)
        return E + ect

    def eval_t(t):
        b, f, p, b_sum = alg3(t)
        R = energy(b, f, p) + lam * t
        return b, f, p, b_sum, R

    # ---- `_auto_bounds`: bracket t from the scenario itself --------------
    G_ab = pmax * hg / N0

    def b_of_t(t):
        tau = t - dl - Jc / fmax
        tgt = jnp.where(tau > 0, Hc / jnp.maximum(tau, 1e-30), big)
        return inv(G_ab, tgt, B)

    def ab_body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        # Strict < B: a pegged single real user sums to exactly B (the
        # padded rows only add ~B*2^-iters) — see core.sroa._auto_bounds.
        ok = jnp.sum(b_of_t(mid), axis=1, keepdims=True) < B
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    ones = jnp.ones_like(B)
    _, t_min = jax.lax.fori_loop(0, t_iters, ab_body,
                                 (ones * t_low, ones * t_up))
    n_eff = jnp.maximum(jnp.sum((Hc > 0).astype(jnp.float32),
                                axis=1, keepdims=True), 1.0)
    b_eq = jnp.broadcast_to(B / n_eff, Hc.shape)
    T_eq = Hc / jnp.maximum(_rate(b_eq, G_ab), 1e-30)
    t_naive = jnp.max(T_eq + Jc / fmax + dl, axis=1, keepdims=True)
    t_lo0 = 0.95 * t_min
    factor = jnp.clip(8.0 / jnp.maximum(lam, 1e-30), 8.0, 2e4)
    t_up0 = jnp.maximum(factor * t_naive, 2.0 * t_lo0)

    # ---- Algorithm 4: value-guided bisection on t ------------------------
    b0, f0, p0, bs0, R0 = eval_t(t_up0)
    R_init = jnp.where(bs0 > B * (1.0 + 1e-3), big, R0)

    def t_body(_, carry):
        t_lo, t_up, R_star, bb, fb, pb, tb, Rb, bsb = carry
        act = (t_up - t_lo) / t_up > eps2
        t = 0.5 * (t_lo + t_up)
        b, f, p, bs, R = eval_t(t)
        infeasible = bs > B * (1.0 + 1e-3)
        improved = jnp.logical_and(~infeasible, R <= R_star)
        n_lo = jnp.where(infeasible | (R > R_star), t, t_lo)
        n_up = jnp.where(improved, t, t_up)
        n_Rs = jnp.where(improved, R, R_star)
        upd = improved                     # (BP, 1) broadcasts over users
        return (jnp.where(act, n_lo, t_lo), jnp.where(act, n_up, t_up),
                jnp.where(act, n_Rs, R_star),
                jnp.where(act & upd, b, bb), jnp.where(act & upd, f, fb),
                jnp.where(act & upd, p, pb), jnp.where(act & upd, t, tb),
                jnp.where(act & upd, R, Rb), jnp.where(act & upd, bs, bsb))

    carry = (t_lo0, t_up0, R_init, b0, f0, p0, t_up0, R0, bs0)
    carry = jax.lax.fori_loop(0, t_iters, t_body, carry)
    _, _, _, bb, fb, pb, tb, Rb, bsb = carry

    b_ref[...] = bb
    f_ref[...] = fb
    p_ref[...] = pb
    feas = (bsb <= B * (1.0 + 1e-3)).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    stat = jnp.where(lane == 0, tb,
                     jnp.where(lane == 1, Rb,
                               jnp.where(lane == 2, bsb,
                                         jnp.where(lane == 3, feas, 0.0))))
    s_ref[...] = stat


def sroa_solve_pallas(A, J, H, delta, h, f_max, p_max, B, b_max, N0, lam,
                      E_cloud_total, *, b_iters: int = 42, f_iters: int = 40,
                      p_iters: int = 36, t_iters: int = 48,
                      eps0: float = 1e-4, eps1: float = 1e-4,
                      eps2: float = 1e-4, t_low: float = 1.0,
                      t_up: float = 3e7, interpret: bool):
    """Fused SROA solve for P independent problems in one kernel launch.

    Per-user operands (A, J, H, delta, h, f_max, p_max): (P, N) float32.
    Per-problem operands (B, b_max, N0, lam, E_cloud_total): (P,) float32.
    Returns (b, f, p) as (P, N) plus (t, R, b_sum, feasible) as (P,).
    """
    A = jnp.asarray(A, jnp.float32)
    P, N = A.shape
    n_pad = (-N) % LANES
    p_pad = (-P) % BLOCK_P

    def pad_u(x, fill):
        x = jnp.asarray(x, jnp.float32)
        return jnp.pad(x, ((0, p_pad), (0, n_pad)), constant_values=fill)

    # Neutral padding == mask_constants: A = J = H = delta = 0, h = 1;
    # f_max/p_max = 1 keeps every divide conditioned.  Padded problems
    # carry harmless positive scalars.
    Ap, Jp, Hp, Dp = (pad_u(x, 0.0) for x in (A, J, H, delta))
    Gp = pad_u(h, 1.0)
    Fp = pad_u(f_max, 1.0)
    Pp = pad_u(p_max, 1.0)

    def pad_s(x, fill):
        x = jnp.broadcast_to(jnp.asarray(x, jnp.float32), (P,))
        return jnp.pad(x, (0, p_pad), constant_values=fill)

    scal = jnp.stack([pad_s(B, 1.0), pad_s(b_max, 1.0), pad_s(N0, 1.0),
                      pad_s(lam, 1.0), pad_s(E_cloud_total, 0.0),
                      jnp.zeros((P + p_pad,), jnp.float32),
                      jnp.zeros((P + p_pad,), jnp.float32),
                      jnp.zeros((P + p_pad,), jnp.float32)], axis=1)

    Np = N + n_pad
    Pt = P + p_pad
    uspec = pl.BlockSpec((BLOCK_P, Np), lambda i: (i, 0))
    sspec = pl.BlockSpec((BLOCK_P, 8), lambda i: (i, 0))
    stspec = pl.BlockSpec((BLOCK_P, LANES), lambda i: (i, 0))
    kern = functools.partial(
        _solve_kernel, b_iters=b_iters, f_iters=f_iters, p_iters=p_iters,
        t_iters=t_iters, eps0=eps0, eps1=eps1, eps2=eps2, t_low=t_low,
        t_up=t_up)
    b, f, p, stat = pl.pallas_call(
        kern,
        grid=(Pt // BLOCK_P,),
        in_specs=[uspec] * 7 + [sspec],
        out_specs=[uspec, uspec, uspec, stspec],
        out_shape=[jax.ShapeDtypeStruct((Pt, Np), jnp.float32)] * 3
        + [jax.ShapeDtypeStruct((Pt, LANES), jnp.float32)],
        interpret=interpret,
        name="sroa_solve",
    )(Ap, Jp, Hp, Dp, Gp, Fp, Pp, scal)
    return (b[:P, :N], f[:P, :N], p[:P, :N], stat[:P, 0], stat[:P, 1],
            stat[:P, 2], stat[:P, 3] > 0.5)
