"""Plain reference of the planning path: the paper's cost model (eqs 4-15),
SROA (Algorithms 2-4) and one round of the single-move neighbourhood search.

A configuration with a compression ladder (DESIGN.md D11) adds a per-user
level ``comp`` (N,) and the ladder's factors, ``ladder`` = one
(bytes_factor, epoch_factor) pair per rung, passed like ``caps``: a user's
cycles a sample are ``c * cycle_mult * epoch_factor[comp]`` and its upload
``s_bits * size_mult * bytes_factor[comp]``, and the neighbourhood gains the
rows that change one user's level.  Without ``comp`` every function runs
the ladder-free code.

Written in straightforward ``jax.numpy`` against plain arrays, with no
kernels and no batching tricks, and importing nothing of the program: a cell
is a dict of the scenario's arrays (``CELL_KEYS``).  ``dtype`` selects the
precision; the benchmark runs it in float32, as the configuration states,
and the control runs the same code in bfloat16.

The arithmetic follows arXiv 2309.09253 and the program's documented
solver settings (``SroaConfig``: bisection tolerances 1e-4, iteration caps
from the configuration file, derived deadline bounds).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

BIG = 1e30
LN2 = math.log(2.0)
CELL_KEYS = ("gain", "gain_cloud", "B_edges", "B_cloud", "p_edge", "c", "D",
             "f_max", "p_max", "s_bits", "alpha", "N0", "L", "K", "I",
             "cycle_mult", "size_mult", "user_pos", "edge_pos")


@functools.lru_cache(maxsize=None)
def _tol(caps: tuple) -> dict:
    b, f, p, t = caps
    return dict(b_iters=b, f_iters=f, p_iters=p, t_iters=t, eps=1e-4,
                t_low=1.0, t_up=3e7)


def _cloud(cell):
    snr = cell["gain_cloud"] * cell["p_edge"] / (cell["N0"] * cell["B_cloud"])
    T = cell["s_bits"] / (cell["B_cloud"] * jnp.log2(1.0 + snr))
    return T, cell["p_edge"] * T


def loads(cell, comp=None, ladder=None):
    """Per-user cycles a sample and upload bits: the device tier's
    multipliers and, with ``comp``, the deployed rung's factors."""
    c = cell["c"] * cell["cycle_mult"]
    s = cell["s_bits"] * cell["size_mult"]
    if comp is not None:
        dt = c.dtype
        c = c * jnp.asarray([e for _, e in ladder], dt)[comp]
        s = s * jnp.asarray([b for b, _ in ladder], dt)[comp]
    return c, s


def constants(cell, assign, mask, comp=None, ladder=None):
    """Per-user constants of problem (17): A, J, H, delta, h, E_cloud."""
    M = cell["gain"].shape[1]
    dt = cell["gain"].dtype
    psi = jax.nn.one_hot(assign, M, dtype=dt) * mask.astype(dt)[:, None]
    occ = psi.sum(axis=0) > 0
    T_cl, E_cl = _cloud(cell)
    T_cl = jnp.where(occ, T_cl, 0.0)
    E_cl = jnp.where(occ, E_cl, 0.0)
    IKL = cell["I"] * cell["K"] * cell["L"]
    c, s = loads(cell, comp, ladder)
    A = 0.5 * cell["alpha"] * IKL * c * cell["D"]
    J = IKL * c * cell["D"]
    H = cell["I"] * cell["K"] * s * jnp.ones_like(c)
    delta = cell["I"] * jnp.sum(psi * T_cl[None, :], axis=1)
    h = jnp.sum(psi * cell["gain"], axis=1)
    z = lambda x: jnp.where(mask, x, 0.0)  # noqa: E731
    return (z(A), z(J), z(H), z(delta), jnp.where(mask, h, 1.0),
            cell["I"] * jnp.sum(E_cl))


def _rate(b, G):
    bs = jnp.maximum(b, 1e-12)
    return jnp.where(b > 0, bs * jnp.log1p(G / bs) / LN2, 0.0)


def _invert(G, target, b_max, iters):
    """Smallest b with rate(b) >= target, by bisection; b_max if none."""
    feas = _rate(jnp.full_like(G, b_max), G) >= target

    def body(_, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        ok = _rate(mid, G) >= target
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    _, hi = lax.fori_loop(0, iters, body,
                          (jnp.zeros_like(G), jnp.full_like(G, b_max)))
    return jnp.where(feas, hi, b_max)


def sroa(cell, assign, mask, lam, caps: tuple, comp=None, ladder=None):
    """Algorithm 4 (with 2 and 3 nested) for one assignment.

    Returns (b, f, p, t, R, b_sum) with R = E_sum + lam * t.
    """
    tol = _tol(caps)
    A, J, H, delta, h, E_ct = constants(cell, assign, mask, comp, ladder)
    B = jnp.sum(cell["B_edges"])
    f_max, p_max, N0 = cell["f_max"], cell["p_max"], cell["N0"]
    dt = h.dtype
    eps = jnp.asarray(tol["eps"], dt)

    def alg2(p, t):
        G = p * h / N0
        den = t - delta - LN2 * H / jnp.maximum(G, 1e-30)
        f_lo = jnp.clip(jnp.where(den > 0, J / jnp.maximum(den, 1e-30), f_max),
                        0.0, f_max)

        def b_of(f):
            tau = t - delta - J / jnp.maximum(f, 1.0)
            tgt = jnp.where(tau > 0, H / jnp.maximum(tau, 1e-30), BIG)
            return _invert(G, tgt, B, tol["b_iters"])

        def cond(c):
            lo, hi, it = c
            gap = jnp.max((hi - lo) / jnp.maximum(hi, 1.0))
            return (gap > eps) & (it < tol["f_iters"])

        def body(c):
            lo, hi, it = c
            f = 0.5 * (lo + hi)
            spare = jnp.sum(b_of(f)) < B
            return jnp.where(spare, lo, f), jnp.where(spare, f, hi), it + 1

        _, f, _ = lax.while_loop(cond, body, (f_lo, f_max, 0))
        b = b_of(f)
        return b, f, jnp.sum(b)

    def alg3(t):
        gamma = H / B
        eta = t - delta - J / f_max
        zeta = N0 * B / h
        expo = jnp.clip(gamma / jnp.maximum(eta, 1e-30), 0.0, 60.0)
        p_lo = jnp.clip(jnp.where(eta > 0, zeta * (2.0 ** expo - 1.0), p_max),
                        0.0, p_max)

        def cond(c):
            lo, hi, it = c
            gap = jnp.max((hi - lo) / jnp.maximum(hi, 1e-12))
            return (gap > eps) & (it < tol["p_iters"])

        def body(c):
            lo, hi, it = c
            p = 0.5 * (lo + hi)
            spare = alg2(p, t)[2] < B
            return jnp.where(spare, lo, p), jnp.where(spare, p, hi), it + 1

        _, p, _ = lax.while_loop(cond, body, (p_lo, p_max, 0))
        b, f, b_sum = alg2(p, t)
        return b, f, p, b_sum

    def energy(b, f, p):
        G = p * h / N0
        T_com = jnp.where(b > 0, H / jnp.maximum(_rate(b, G), 1e-30), BIG)
        return jnp.sum(p * T_com + A * f ** 2) + E_ct

    def at(t):
        b, f, p, b_sum = alg3(t)
        return b, f, p, b_sum, energy(b, f, p) + lam * t

    # Deadline bounds from the cell itself (t_min at f_max, p_max).
    G_max = p_max * h / N0

    def bound_body(_, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        tau = mid - delta - J / f_max
        tgt = jnp.where(tau > 0, H / jnp.maximum(tau, 1e-30), BIG)
        ok = jnp.sum(_invert(G_max, tgt, B, tol["b_iters"])) < B
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    _, t_min = lax.fori_loop(0, tol["t_iters"], bound_body,
                             (jnp.asarray(tol["t_low"], dt),
                              jnp.asarray(tol["t_up"], dt)))
    n_eff = jnp.maximum(jnp.sum((H > 0).astype(dt)), 1.0)
    b_eq = jnp.broadcast_to(B / n_eff, h.shape)
    t_naive = jnp.max(H / jnp.maximum(_rate(b_eq, G_max), 1e-30)
                      + J / f_max + delta)
    t_lo = 0.95 * t_min
    t_up = jnp.maximum(jnp.clip(8.0 / jnp.maximum(lam, 1e-30), 8.0, 2e4)
                       * t_naive, 2.0 * t_lo)
    over = B * (1.0 + 1e-3)

    b0, f0, p0, s0, R0 = at(t_up)
    best0 = (b0, f0, p0, t_up, R0, s0)

    def cond(c):
        lo, hi, _, _, it = c
        return ((hi - lo) / hi > eps) & (it < tol["t_iters"])

    def body(c):
        lo, hi, R_star, best, it = c
        t = 0.5 * (lo + hi)
        b, f, p, s, R = at(t)
        bad = s > over
        better = (~bad) & (R <= R_star)
        lo = jnp.where(bad | (R > R_star), t, lo)
        hi = jnp.where(better, t, hi)
        best = jax.tree.map(lambda n, o: jnp.where(better, n, o),
                            (b, f, p, t, R, s), best)
        return lo, hi, jnp.where(better, R, R_star), best, it + 1

    carry = (t_lo, t_up, jnp.where(s0 > over, BIG, R0), best0, 0)
    best = lax.while_loop(cond, body, carry)[3]
    return best


def evaluate(cell, assign, b, f, p, lam, mask, comp=None, ladder=None):
    """Eq 15 objective R and the per-edge costs R_m (eq 23) of a plan."""
    M = cell["gain"].shape[1]
    dt = cell["gain"].dtype
    psi = jax.nn.one_hot(assign, M, dtype=dt) * mask.astype(dt)[:, None]
    h = jnp.sum(psi * cell["gain"], axis=1)
    c, s = loads(cell, comp, ladder)
    T_cmp = cell["L"] * c * cell["D"] / jnp.maximum(f, 1.0)
    E_cmp = 0.5 * cell["alpha"] * cell["L"] * f ** 2 * c * cell["D"]
    bs = jnp.maximum(b, 1e-9)
    r = jnp.where(b > 0, bs * jnp.log1p(h * p / (cell["N0"] * bs)) / LN2, 0.0)
    T_com = jnp.where(r > 0, s / jnp.maximum(r, 1e-9), BIG)
    E_com = p * T_com
    occ = psi.sum(axis=0) > 0
    T_m = cell["K"] * jnp.max(jnp.where(psi > 0, (T_cmp + T_com)[:, None],
                                        -BIG), axis=0)
    T_m = jnp.where(occ, T_m, 0.0)
    E_m = cell["K"] * jnp.sum(psi * (E_cmp + E_com)[:, None], axis=0)
    T_cl, E_cl = _cloud(cell)
    T_cl = jnp.where(occ, T_cl, 0.0)
    E_cl = jnp.where(occ, E_cl, 0.0)
    R = cell["I"] * (jnp.sum(E_cl + E_m) + lam * jnp.max(T_cl + T_m))
    R_m = cell["I"] * ((E_cl + E_m) + lam * (T_cl + T_m))
    return R, R_m


def neighbourhood(assign, mask, M: int):
    """Row 0 = the pattern itself; then every movable user to every other
    edge.  Returns (cands (1+N(M-1), N), valid)."""
    N = assign.shape[0]
    dst = (assign[:, None] + jnp.arange(1, M)[None, :]) % M      # (N, M-1)
    rows = jnp.repeat(jnp.arange(N), M - 1)
    moves = jnp.tile(assign[None, :], (N * (M - 1), 1))
    moves = moves.at[jnp.arange(N * (M - 1)), rows].set(dst.reshape(-1))
    cands = jnp.concatenate([assign[None, :], moves])
    valid = jnp.concatenate([jnp.ones((1,), bool), jnp.repeat(mask, M - 1)])
    return cands, valid


def joint_neighbourhood(assign, comp, mask, M: int, L: int):
    """The single moves, each keeping every level, then every movable user
    to each other rung (cyclically: its level + 1, ..., + L-1 mod L) on
    the same assignment.  Returns (cands, comps, valid), 1+N(M-1)+N(L-1)
    rows."""
    N = assign.shape[0]
    comp, mask = jnp.asarray(comp), jnp.asarray(mask)
    cands, valid = neighbourhood(assign, mask, M)
    users = jnp.repeat(jnp.arange(N), L - 1)
    lv = (comp[users] + jnp.tile(jnp.arange(1, L), N)) % L
    bumps = jnp.tile(comp[None, :], (N * (L - 1), 1))
    bumps = bumps.at[jnp.arange(N * (L - 1)), users].set(lv)
    cands = jnp.concatenate([cands, jnp.tile(assign[None, :],
                                             (N * (L - 1), 1))])
    comps = jnp.concatenate([jnp.tile(comp[None, :], (1 + N * (M - 1), 1)),
                             bumps])
    return cands, comps, jnp.concatenate([valid, mask[users]])


def score(cell, assign, mask, lam, caps: tuple, comp=None, ladder=None):
    """Eq 15 R of an assignment with its SROA allocation."""
    b, f, p, _, _, _ = sroa(cell, assign, mask, lam, caps, comp, ladder)
    return evaluate(cell, assign, b, f, p, lam, mask, comp, ladder)[0]


def score_neighbourhood(cell, assign, mask, lam, caps: tuple, comp=None,
                        ladder=None):
    """R of every single-move neighbour (invalid rows -> +inf); with
    ``comp``, of the joint neighbourhood."""
    M = cell["gain"].shape[1]
    if comp is None:
        cands, valid = neighbourhood(assign, mask, M)
        R = jax.vmap(lambda a: score(cell, a, mask, lam, caps))(cands)
    else:
        cands, comps, valid = joint_neighbourhood(assign, comp, mask, M,
                                                  len(ladder))
        R = jax.vmap(lambda a, cp: score(cell, a, mask, lam, caps, cp,
                                         ladder))(cands, comps)
    return cands, jnp.where(valid, R, jnp.inf)


def cast(cell: dict, dtype) -> dict:
    """The cell's float arrays in ``dtype`` (integers stay as they are)."""
    return {k: jnp.asarray(cell[k], dtype) for k in CELL_KEYS}
