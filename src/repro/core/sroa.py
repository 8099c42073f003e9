"""SROA — Spectrum Resource Optimization Algorithm (paper §IV, Algs 2-4).

Given a user->edge assignment, SROA minimizes
``R = E_sum + lambda * T_sum`` over (b, f, p) via three nested binary
searches, exactly following the paper:

* Algorithm 2: optimal (b, f) for fixed (p, t).  All N users' frequency
  intervals are bisected in lockstep (the paper updates every f_n from the
  single scalar predicate ``b_sum < B``); the innermost per-user bandwidth
  bisection inverts the monotone rate function b*log2(1 + G/b) (Lemma 1).
* Algorithm 3: optimal p for fixed t, bounded below by Lemma 2.
* Algorithm 4: outer bisection on the deadline t, tracking the best R.

Everything is vectorized over users and wrapped in ``lax.while_loop`` with
both relative-tolerance and iteration-cap stopping, so a full solve is one
XLA computation (jit-able, differentiable in the leaves we don't branch on).

The innermost bandwidth inversion is the compute hot-spot when planning for
fleet-scale N (the paper's complexity analysis §IV-C is dominated by it).
:func:`invert_rate` unrolls its fixed-trip bisection into the body of a
one-trip device loop, so XLA fuses all its steps into one op per call
rather than launching one op per step.  The op is compute-bound, so its
cost is the number of (8, 128) vector tiles it runs over.  Batched as the
assignment engine batches it, (cells, candidates, N = 50 users), the users
sit on the 128-wide lane axis and fill 38% of each tile; so under ``vmap``
Algorithm 2's f search (:func:`_alg2_search`) runs the bisection on one
lane-dense ``(rows, 128)`` stream of the whole batch instead, wherever
that at least halves the tiles (:func:`invert_tiles`), streaming G and
b_max once per search and each step's target in and b out.  Every
element gets the same ops either way, so the results are bitwise the
same.
``repro.kernels.sroa_bisect`` provides a Pallas TPU kernel for the
inversion, validated against :func:`invert_rate` (the pure-jnp oracle) in
tests.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.system_model import SroaConstants, sroa_constants
from repro.core.wireless import LN2, Scenario

_BIG = 1e30


@dataclasses.dataclass(frozen=True)
class SroaConfig:
    eps0: float = 1e-4       # Algorithm 2 tolerance (f bisection)
    eps1: float = 1e-4       # Algorithm 3 tolerance (p bisection)
    eps2: float = 1e-4       # Algorithm 4 tolerance (t bisection)
    b_iters: int = 42        # innermost bandwidth bisection iterations
    f_iters: int = 40        # iteration caps (tolerance usually hits first)
    p_iters: int = 36
    t_iters: int = 48
    t_low: float = 1.0       # seconds (whole-training deadline range);
    t_up: float = 3e7        # only used when auto_bounds=False
    auto_bounds: bool = True  # derive [t_low, t_up] from the scenario
    refine_iters: int = 0    # >0: beyond-paper golden-section polish of t*
    use_pallas: bool = False  # route invert_rate through the Pallas kernel
    fused: bool = False      # run Algs 2-4 in ONE Pallas kernel (see D9)


class SroaResult(NamedTuple):
    b: jnp.ndarray         # (N,) Hz
    f: jnp.ndarray         # (N,) Hz
    p: jnp.ndarray         # (N,) W
    t: jnp.ndarray         # ()   optimal deadline t*
    R: jnp.ndarray         # ()   objective value tracked by Algorithm 4
    b_sum: jnp.ndarray     # ()   total bandwidth used
    feasible: jnp.ndarray  # ()   bool, b_sum <= B at the returned solution


def rate_fn(b: jnp.ndarray, G: jnp.ndarray) -> jnp.ndarray:
    """h(b) = b log2(1 + G/b); monotone increasing, sup = G/ln2 (Lemma 1).

    Uses log1p for accuracy in the large-b/small-SNR regime.
    """
    b_safe = jnp.maximum(b, 1e-12)
    return jnp.where(b > 0, b_safe * jnp.log1p(G / b_safe) / LN2, 0.0)


def invert_rate(G: jnp.ndarray, target: jnp.ndarray, b_max,
                iters: int = 42) -> jnp.ndarray:
    """Smallest b with b*log2(1+G/b) >= target (bisection; jnp oracle).

    Returns b_max where even b_max cannot reach the target (infeasible);
    callers detect this via ``rate_fn(b, G) < target``.

    The ``iters`` steps are unrolled, so XLA fuses them into one op, inside
    a ``while_loop`` that runs once on a flag.  XLA cannot see the trip
    count and keeps that loop, so the result reaches the caller through
    memory, as the step-by-step loop's did.  Unrolled inline instead, XLA
    would fold the producers of ``G`` and ``target`` into the steps
    (``(x / y) / b`` -> ``x / (y * b)``) and fuse the caller's ``b_sum``
    reduce with them, changing its summation order; both change bits.
    Kept, the result and the caller's sums are bitwise the rolled loop's.
    """
    feas = rate_fn(jnp.full_like(G, b_max), G) >= target

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        ok = rate_fn(mid, G) >= target
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    def bisect(carry):
        _, hi = carry
        _, hi = lax.fori_loop(0, iters, body, (jnp.zeros_like(G), hi),
                              unroll=True)
        return False, hi

    _, hi = lax.while_loop(lambda carry: carry[0], bisect,
                           (True, jnp.full_like(G, b_max)))
    return jnp.where(feas, hi, b_max)


# Lane-dense inversion under vmap.  A tile is one (8, 128) f32 vreg.
TILE = 8 * 128
# Fewest tiles a lane-dense stream must fill before the relayout into it
# and back pays.  Not set from a measurement: every engine bucket the
# benchmark's cells run fills 40 or more tiles and the re-pricing 1-2, so
# any floor from 3 to 40 splits them the same way.
_DENSE_MIN_TILES = 16


def invert_tiles(shape) -> tuple[int, int, bool]:
    """How a batched inversion of ``shape`` = (..., S, N) runs.

    Returns (elements, tiles, dense): the tiles are (8, 128) vregs, of the
    batch as laid out (N on the lanes, S on the sublanes) or, where that at
    least halves them and fills at least ``_DENSE_MIN_TILES``, of one
    lane-dense stream of every element (``dense``).
    """
    elements = math.prod(shape)
    tiled = (math.prod(shape[:-2]) * -(-shape[-2] // 8)
             * -(-shape[-1] // 128))
    dense = -(-elements // TILE)
    if dense >= _DENSE_MIN_TILES and 2 * dense <= tiled:
        return elements, dense, True
    return elements, tiled, False


def _dense_inverter(G, b_max, iters: int):
    """``target -> invert_rate(G, target, b_max)`` over a batch, G (..., N)
    and b_max (...), run on one lane-dense ``(rows, 128)`` stream of every
    element, padded to whole tiles (G = 1, target = 0, b_max = 1, which
    bisect harmlessly).  G and b_max are streamed here, once; each call
    streams its target in and its b back out."""
    n = G.size
    rows = -(-n // TILE) * 8

    def stream(x, fill):
        return jnp.pad(x.reshape(-1), (0, rows * 128 - n),
                       constant_values=fill).reshape(rows, 128)

    if b_max.ndim < G.ndim:                  # one b_max per problem
        b_max = b_max[..., None]
    Gs = stream(G, 1.0)
    bms = stream(jnp.broadcast_to(b_max, G.shape), 1.0)

    def invert(target):
        b = invert_rate(Gs, stream(target, 0.0), bms, iters)
        return b.reshape(-1)[:n].reshape(G.shape)

    return invert


@functools.lru_cache(maxsize=None)
def _pallas_invert_nd(iters: int):
    """Arbitrary-rank Pallas inversion that keeps flattening under vmap.

    ``kops.sroa_invert_rate_batched`` already collapses every leading axis
    into one kernel launch, so the batching rule for *further* vmap levels
    (e.g. the assignment engine's candidate axis nested under the fleet's
    cell axis) just broadcasts the unbatched operands and recurses into the
    same custom-vmap function one rank higher.
    """
    from jax.custom_batching import custom_vmap

    from repro.kernels import ops as kops

    @custom_vmap
    def inv_nd(G, target, b_max):
        # G, target: (..., N); b_max: (...) — one flattened kernel launch.
        return kops.sroa_invert_rate_batched(G, target, b_max, iters=iters)

    @inv_nd.def_vmap
    def _rule_nd(axis_size, in_batched, G, target, b_max):  # noqa: ANN001
        g_b, t_b, bm_b = in_batched
        if not g_b:
            G = jnp.broadcast_to(G, (axis_size,) + G.shape)
        if not t_b:
            target = jnp.broadcast_to(target, (axis_size,) + target.shape)
        if not bm_b:
            b_max = jnp.broadcast_to(b_max, (axis_size,) + jnp.shape(b_max))
        return inv_nd(G, target, b_max), True

    return inv_nd


@functools.lru_cache(maxsize=None)
def _pallas_invert(iters: int):
    """Pallas inversion with a batching rule that fills the kernel tiles.

    Unbatched, this is the plain (N,) kernel call.  Under `jax.vmap` (the
    fleet path: B scenarios x N users) the custom rule flattens the whole
    (B, N) batch into one kernel launch so small per-cell user counts pack
    full (8 x 128) VPU tiles instead of padding each cell separately.
    Deeper nesting (candidates-within-cells) is handled by
    :func:`_pallas_invert_nd`, whose rule flattens every additional level.
    """
    from jax.custom_batching import custom_vmap

    from repro.kernels import ops as kops

    @custom_vmap
    def inv(G, target, b_max):
        return kops.sroa_invert_rate(G, target, b_max, iters=iters)

    @inv.def_vmap
    def _rule(axis_size, in_batched, G, target, b_max):  # noqa: ANN001
        g_b, t_b, bm_b = in_batched
        if not g_b:
            G = jnp.broadcast_to(G, (axis_size,) + G.shape)
        if not t_b:
            target = jnp.broadcast_to(target, (axis_size,) + target.shape)
        bm = b_max if bm_b else jnp.broadcast_to(b_max, (axis_size,))
        out = _pallas_invert_nd(iters)(G, target, bm)
        return out, True

    return inv


@functools.lru_cache(maxsize=None)
def _fused_solver(cfg: "SroaConfig"):
    """Whole-SROA Pallas solver with a vmap rule that keeps flattening.

    Like :func:`_pallas_invert_nd` but for the ENTIRE Algorithm 2-4 nest:
    every extra vmap level (the engine's candidate axis, the fleet's cell
    axis) broadcasts unbatched operands and recurses one rank higher, so
    arbitrarily nested batching still lowers to one kernel launch over the
    flattened problem axis.
    """
    from jax.custom_batching import custom_vmap

    from repro.kernels import ops as kops

    kw = dict(b_iters=cfg.b_iters, f_iters=cfg.f_iters,
              p_iters=cfg.p_iters, t_iters=cfg.t_iters, eps0=cfg.eps0,
              eps1=cfg.eps1, eps2=cfg.eps2, t_low=cfg.t_low, t_up=cfg.t_up)

    @custom_vmap
    def solve_nd(A, J, H, delta, h, f_max, p_max, B, b_max, N0, lam, ect):
        return kops.sroa_solve_batched(A, J, H, delta, h, f_max, p_max,
                                       B, b_max, N0, lam, ect, **kw)

    @solve_nd.def_vmap
    def _rule(axis_size, in_batched, *args):  # noqa: ANN001
        args = tuple(
            a if ab else jnp.broadcast_to(a, (axis_size,) + jnp.shape(a))
            for a, ab in zip(args, in_batched))
        out = solve_nd(*args)
        return out, tuple(True for _ in out)

    return solve_nd


def _solve_constants_fused(consts: SroaConstants, B, b_max, f_max, p_max,
                           N0, lam, cfg: "SroaConfig") -> "SroaResult":
    """Fused-kernel equivalent of :func:`solve_constants_impl`.

    Agrees with the jnp path to bisection tolerance (not bitwise — the
    kernel carries best-so-far state per problem rather than per tree
    node); the parity contract is tested in ``tests/test_kernels.py``.
    """
    shape = jnp.shape(consts.h)
    f_max = jnp.broadcast_to(jnp.asarray(f_max, jnp.float32), shape)
    p_max = jnp.broadcast_to(jnp.asarray(p_max, jnp.float32), shape)
    b, f, p, t, R, b_sum, feas = _fused_solver(cfg)(
        consts.A, consts.J, consts.H, consts.delta, consts.h, f_max, p_max,
        jnp.asarray(B, jnp.float32), jnp.asarray(b_max, jnp.float32),
        jnp.asarray(N0, jnp.float32), jnp.asarray(lam, jnp.float32),
        jnp.asarray(consts.E_cloud_total, jnp.float32))
    return SroaResult(b=b, f=f, p=p, t=t, R=R, b_sum=b_sum, feasible=feas)


# --------------------------------------------------------------------------
# Algorithm 2: optimal (b, f) with fixed (p, t)
# --------------------------------------------------------------------------
def _alg2_bisect(invert, t, delta, J, H, f_lo0, f_hi0, B,
                 cfg: SroaConfig):
    """Algorithm 2's lockstep bisection on f for one problem, with
    ``invert(target) -> b`` the inner inversion.  Returns (b, f)."""
    def b_of_f(f):
        tau = t - delta - J / jnp.maximum(f, 1.0)
        target = jnp.where(tau > 0, H / jnp.maximum(tau, 1e-30), _BIG)
        return invert(target)

    def cond(carry):
        f_lo, f_hi, it = carry
        gap = jnp.max((f_hi - f_lo) / jnp.maximum(f_hi, 1.0))
        return jnp.logical_and(gap > cfg.eps0, it < cfg.f_iters)

    def body(carry):
        f_lo, f_hi, it = carry
        f = 0.5 * (f_lo + f_hi)
        b_sum = jnp.sum(b_of_f(f))
        spare = b_sum < B             # bandwidth to spare -> lower f (save E)
        f_hi = jnp.where(spare, f, f_hi)
        f_lo = jnp.where(spare, f_lo, f)
        return f_lo, f_hi, it + 1

    f_lo, f_hi, _ = lax.while_loop(cond, body, (f_lo0, f_hi0, 0))
    f = f_hi                          # feasible side (b_sum <= B when any f is)
    return b_of_f(f), f


def _alg2_bisect_dense(G, b_max, t, delta, J, H, f_lo0, f_hi0, B,
                       cfg: SroaConfig):
    """:func:`_alg2_bisect` over a batch of problems, as ``vmap`` runs it
    (the loop steps while any problem steps, and a problem that has
    stopped keeps its carry), with the inversion on lane-dense tiles
    (:func:`_dense_inverter`).  Per user (..., N): G, delta, J, H, f_lo0,
    f_hi0; per problem (...): b_max, t, B."""
    invert = _dense_inverter(G, b_max, cfg.b_iters)
    t = t[..., None]

    def b_of_f(f):
        tau = t - delta - J / jnp.maximum(f, 1.0)
        target = jnp.where(tau > 0, H / jnp.maximum(tau, 1e-30), _BIG)
        return invert(target)

    def stepping(carry):
        f_lo, f_hi, it = carry
        gap = jnp.max((f_hi - f_lo) / jnp.maximum(f_hi, 1.0), axis=-1)
        return jnp.logical_and(gap > cfg.eps0, it < cfg.f_iters)

    def body(carry):
        f_lo, f_hi, it = carry
        on = stepping(carry)
        f = 0.5 * (f_lo + f_hi)
        b_sum = jnp.sum(b_of_f(f), axis=-1)
        spare = (b_sum < B)[..., None]
        step = on[..., None]
        return (jnp.where(step, jnp.where(spare, f_lo, f), f_lo),
                jnp.where(step, jnp.where(spare, f, f_hi), f_hi),
                jnp.where(on, it + 1, it))

    it0 = jnp.zeros(G.shape[:-1], jnp.int32)
    f_lo, f_hi, _ = lax.while_loop(lambda c: jnp.any(stepping(c)), body,
                                   (f_lo0, f_hi0, it0))
    return b_of_f(f_hi), f_hi


@functools.lru_cache(maxsize=None)
def _alg2_search(cfg: SroaConfig):
    """Algorithm 2's f search on the jnp inversion, with a batching rule
    that runs a vmapped search on lane-dense tiles.

    Unbatched, G is (N,) and this is :func:`_alg2_bisect` on
    :func:`invert_rate`.  Every vmap level re-enters the rule one rank
    higher, so under the engine's cells x candidates the whole batch
    arrives at once; where :func:`invert_tiles` says so it runs as
    :func:`_alg2_bisect_dense`, with G and b_max streamed once for the
    whole search, else as the plain search vmapped.  Every element gets
    the same ops either way, so the results are bitwise the same.
    """
    from jax.custom_batching import custom_vmap

    def plain(G, b_max, t, delta, J, H, f_lo0, f_hi0, B):
        def invert(target):
            return invert_rate(G, target, b_max, iters=cfg.b_iters)
        return _alg2_bisect(invert, t, delta, J, H, f_lo0, f_hi0, B, cfg)

    @custom_vmap
    def search(G, *args):
        if G.ndim > 1 and invert_tiles(G.shape)[2]:
            return _alg2_bisect_dense(G, *args, cfg)
        fn = plain
        for _ in range(G.ndim - 1):
            fn = jax.vmap(fn)
        return fn(G, *args)

    @search.def_vmap
    def _rule(axis_size, in_batched, *args):  # noqa: ANN001
        args = tuple(
            x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, batched in zip(args, in_batched))
        return search(*args), (True, True)

    return search


@jax.named_scope("sroa.alg2")
def algorithm2(consts: SroaConstants, p: jnp.ndarray, t, B, b_max,
               f_max: jnp.ndarray, N0, cfg: SroaConfig):
    """Returns (b, f, b_sum). Lockstep bisection on f, inner inversion for b."""
    G = p * consts.h / N0
    # Lemma 1 lower bound: f >= J / (t - delta - ln2 * H / G); guard the
    # degenerate case (denominator <= 0 -> infeasible even at b -> inf).
    denom = t - consts.delta - LN2 * consts.H / jnp.maximum(G, 1e-30)
    f_lo0 = jnp.where(denom > 0, consts.J / jnp.maximum(denom, 1e-30), f_max)
    f_lo0 = jnp.clip(f_lo0, 0.0, f_max)
    f_hi0 = f_max
    b_max = jnp.asarray(b_max, jnp.float32)
    if cfg.use_pallas:
        def invert(target):
            return _pallas_invert(cfg.b_iters)(G, target, b_max)
        b, f = _alg2_bisect(invert, t, consts.delta, consts.J, consts.H,
                            f_lo0, f_hi0, B, cfg)
    else:
        f32 = partial(jnp.asarray, dtype=jnp.float32)
        b, f = _alg2_search(cfg)(G, b_max, f32(t), consts.delta, consts.J,
                                 consts.H, f_lo0, jnp.broadcast_to(
                                     f_hi0, G.shape), f32(B))
    return b, f, jnp.sum(b)


# --------------------------------------------------------------------------
# Algorithm 3: optimal p with fixed t
# --------------------------------------------------------------------------
@jax.named_scope("sroa.alg3")
def algorithm3(consts: SroaConstants, t, B, b_max, f_max, p_max, N0,
               cfg: SroaConfig):
    """Returns (b, f, p, b_sum)."""
    # Lemma 2 lower bound at b = b_max, f = f_max.
    gamma = consts.H / b_max
    eta = t - consts.delta - consts.J / f_max
    zeta = N0 * b_max / consts.h
    expo = jnp.clip(gamma / jnp.maximum(eta, 1e-30), 0.0, 60.0)
    p_lo0 = jnp.where(eta > 0, zeta * (2.0 ** expo - 1.0), p_max)
    p_lo0 = jnp.clip(p_lo0, 0.0, p_max)
    p_hi0 = p_max

    def cond(carry):
        p_lo, p_hi, it = carry
        gap = jnp.max((p_hi - p_lo) / jnp.maximum(p_hi, 1e-12))
        return jnp.logical_and(gap > cfg.eps1, it < cfg.p_iters)

    def body(carry):
        p_lo, p_hi, it = carry
        p = 0.5 * (p_lo + p_hi)
        _, _, b_sum = algorithm2(consts, p, t, B, b_max, f_max, N0, cfg)
        spare = b_sum < B             # spare bandwidth -> lower p (save E)
        p_hi = jnp.where(spare, p, p_hi)
        p_lo = jnp.where(spare, p_lo, p)
        return p_lo, p_hi, it + 1

    p_lo, p_hi, _ = lax.while_loop(cond, body, (p_lo0, p_hi0, 0))
    p = p_hi                          # feasible side
    b, f, b_sum = algorithm2(consts, p, t, B, b_max, f_max, N0, cfg)
    return b, f, p, b_sum


# --------------------------------------------------------------------------
# Algorithm 4: outer bisection on t
# --------------------------------------------------------------------------
def _energy(consts: SroaConstants, b, f, p, N0):
    """Total E_sum of problem (17) + the constant cloud term (eq 14)."""
    G = p * consts.h / N0
    T_com = jnp.where(b > 0, consts.H / jnp.maximum(rate_fn(b, G), 1e-30), _BIG)
    E_com = p * T_com                       # already scaled by I*K via H
    E_cmp = consts.A * f ** 2
    return jnp.sum(E_com + E_cmp) + consts.E_cloud_total


@jax.named_scope("sroa.bounds")
def _auto_bounds(consts: SroaConstants, B, f_max, p_max, N0, lam,
                 cfg: SroaConfig):
    """Derive [t_lo, t_up] for Algorithm 4 from the scenario itself.

    t_lo: slightly below the delay-optimal deadline (smallest feasible t at
    f_max/p_max — below it b_sum must exceed B).  t_up: a multiple of the
    zero-optimization equal-split delay; the multiple scales with 1/lam
    because for delay-insensitive objectives (small lam) the optimum sits at
    much larger deadlines (energy keeps falling in t).  The paper only asks
    for "large/small enough" bounds; bounds that track the optimum keep the
    halving steps of the value-guided bisection from stepping over it.
    """
    G = p_max * consts.h / N0

    def b_of_t(t):
        tau = t - consts.delta - consts.J / f_max
        target = jnp.where(tau > 0, consts.H / jnp.maximum(tau, 1e-30), _BIG)
        return invert_rate(G, target, B, iters=cfg.b_iters)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        # Strict: an infeasible deadline pegs a user at b = b_max = B, so a
        # single-user cell sums to EXACTLY B and `<=` would call every t
        # feasible, collapsing t_min to t_low.  A genuinely feasible
        # minimal allocation never lands on B to the last ulp.
        ok = jnp.sum(b_of_t(mid)) < B
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    lo = jnp.asarray(cfg.t_low, jnp.float32)
    hi = jnp.asarray(cfg.t_up, jnp.float32)
    _, t_min = lax.fori_loop(0, cfg.t_iters, body, (lo, hi))

    # Equal-split delay (no optimization at all).  The head count must be
    # the number of *real* users (H > 0) so a padded fleet cell follows the
    # same t-grid as its standalone solve (see fleet/batch.py).
    n_eff = jnp.maximum(jnp.sum((consts.H > 0).astype(jnp.float32)), 1.0)
    b_eq = jnp.broadcast_to(B / n_eff, consts.h.shape)
    T_com = consts.H / jnp.maximum(rate_fn(b_eq, G), 1e-30)
    t_naive = jnp.max(T_com + consts.J / f_max + consts.delta)
    t_lo = 0.95 * t_min
    factor = jnp.clip(8.0 / jnp.maximum(lam, 1e-30), 8.0, 2e4)
    t_up = jnp.maximum(factor * t_naive, 2.0 * t_lo)
    return t_lo, t_up


def solve_constants_impl(consts: SroaConstants, B, b_max, f_max, p_max, N0,
                         lam, cfg: SroaConfig = SroaConfig()) -> SroaResult:
    """Algorithm 4 driver on pre-computed constants (un-jitted).

    The traceable entry point: the assignment engine
    (:mod:`repro.fleet.engine`) vmaps this over a candidate axis *inside*
    its own jitted while_loop (and the fleet path vmaps that again over
    cells), so the jit wrapper lives one level up in
    :func:`solve_constants`.

    With ``cfg.fused`` the whole Algorithm 2-4 nest is delegated to the
    fused Pallas kernel (one launch per flattened batch; see D9).  The
    fused path implements the paper-faithful algorithm only, so the
    beyond-paper ``refine_iters`` polish and manual bounds fall back to
    the jnp path.
    """
    if cfg.fused and cfg.auto_bounds and cfg.refine_iters == 0:
        return _solve_constants_fused(consts, B, b_max, f_max, p_max, N0,
                                      lam, cfg)

    def eval_t(t):
        b, f, p, b_sum = algorithm3(consts, t, B, b_max, f_max, p_max, N0, cfg)
        E_sum = _energy(consts, b, f, p, N0)
        R = E_sum + lam * t
        return b, f, p, b_sum, R

    def eval_t_plus(t):
        """Beyond-paper (SROA+): the paper's nesting minimizes p before f,
        so the power loop can consume all bandwidth slack and pin f at
        f_max (dominant compute energy) when t is large.  Also evaluate
        f-prioritized candidates at fixed power levels and keep the best."""
        best = eval_t(t)
        for scale in (1.0, 1e-1, 1e-2, 1e-3):
            p_c = p_max * scale
            b, f, b_sum = algorithm2(consts, p_c, t, B, b_max, f_max, N0,
                                     cfg)
            p_vec = jnp.broadcast_to(p_c, f.shape)
            R = _energy(consts, b, f, p_vec, N0) + lam * t
            feas = b_sum <= B * (1.0 + 1e-3)
            better = jnp.logical_and(feas, R < best[4])
            best = jax.tree.map(
                lambda new, old: jnp.where(better, new, old),
                (b, f, p_vec, b_sum, R), best)
        return best

    if cfg.auto_bounds:
        t_lo0, t_up0 = _auto_bounds(consts, B, f_max, p_max, N0, lam, cfg)
    else:
        t_lo0 = jnp.asarray(cfg.t_low, jnp.float32)
        t_up0 = jnp.asarray(cfg.t_up, jnp.float32)

    def cond(carry):
        t_lo, t_up, R_star, _, it = carry
        return jnp.logical_and((t_up - t_lo) / t_up > cfg.eps2,
                               it < cfg.t_iters)

    def body(carry):
        t_lo, t_up, R_star, best, it = carry
        t = 0.5 * (t_lo + t_up)
        b, f, p, b_sum, R = eval_t(t)
        infeasible = b_sum > B * (1.0 + 1e-3)
        improved = jnp.logical_and(~infeasible, R <= R_star)
        t_lo = jnp.where(infeasible | (R > R_star), t, t_lo)
        t_up = jnp.where(improved, t, t_up)
        R_star = jnp.where(improved, R, R_star)
        best = jax.tree.map(
            lambda new, old: jnp.where(improved, new, old),
            (b, f, p, t, R, b_sum), best)
        return t_lo, t_up, R_star, best, it + 1

    with jax.named_scope("sroa.alg4"):
        # Seed "best" with the largest deadline (always feasible if anything is).
        b0, f0, p0, bsum0, R0 = eval_t(t_up0)
        init_best = (b0, f0, p0, t_up0, R0, bsum0)
        R_init = jnp.where(bsum0 > B * (1.0 + 1e-3), _BIG, R0)
        carry = (t_lo0, t_up0, R_init, init_best, 0)
        _, _, R_star, best, _ = lax.while_loop(cond, body, carry)
    b, f, p, t, R, b_sum = best

    if cfg.refine_iters > 0:
        # Beyond-paper polish (SROA+): the paper's value-guided bisection is
        # not a correct minimizer of R(t) — it can converge to the wrong
        # basin when R(t) is flat (small lambda).  Globalize with a coarse
        # log-grid scan over [t_lo, t_up], then golden-section around the
        # best bracket.
        def R_at(t):
            _, _, _, b_sum, Rt = eval_t_plus(t)
            return jnp.where(b_sum > B * (1.0 + 1e-3), _BIG, Rt)

        n_grid = 16
        ts = jnp.exp(jnp.linspace(jnp.log(jnp.maximum(t_lo0, 1e-3)),
                                  jnp.log(t_up0), n_grid))

        def grid_body(i, best):
            t_b, R_b = best
            Rt = R_at(ts[i])
            better_i = Rt < R_b
            return (jnp.where(better_i, ts[i], t_b),
                    jnp.where(better_i, Rt, R_b))

        t_g, R_g = lax.fori_loop(0, n_grid, grid_body, (t, R))

        gr = 0.6180339887498949

        def g_body(_, lohi):
            lo, hi = lohi
            x1 = hi - gr * (hi - lo)
            x2 = lo + gr * (hi - lo)
            shrink_hi = R_at(x1) < R_at(x2)
            return (jnp.where(shrink_hi, lo, x1),
                    jnp.where(shrink_hi, x2, hi))

        lo, hi = lax.fori_loop(0, cfg.refine_iters, g_body,
                               (0.5 * t_g, jnp.minimum(2.5 * t_g, t_up0)))
        t_ref = 0.5 * (lo + hi)
        b2, f2, p2, bsum2, R2 = eval_t_plus(t_ref)
        better = jnp.logical_and(bsum2 <= B * (1.0 + 1e-3), R2 < R)
        b, f, p, t, R, b_sum = jax.tree.map(
            lambda new, old: jnp.where(better, new, old),
            (b2, f2, p2, t_ref, R2, bsum2), (b, f, p, t, R, b_sum))

    return SroaResult(b=b, f=f, p=p, t=t, R=R, b_sum=b_sum,
                      feasible=b_sum <= B * (1.0 + 1e-3))


solve_constants = partial(jax.jit, static_argnames=("cfg",))(
    solve_constants_impl)
solve_constants.__doc__ = "Jitted :func:`solve_constants_impl`."


def solve(scn: Scenario, assign: jnp.ndarray, lam,
          cfg: SroaConfig = SroaConfig(),
          comp: jnp.ndarray | None = None, ladder=None) -> SroaResult:
    """SROA for one assignment pattern: the paper's `Algorithm 4` end-to-end.

    ``comp``/``ladder`` (D11) price a fixed per-user compression choice
    into the constants; None keeps the literal paper model.
    """
    consts = sroa_constants(scn, assign, comp=comp, ladder=ladder)
    B = scn.B_open  # == B_total bitwise when no edge mask (D12)
    return solve_constants(consts, B, B, scn.f_max, scn.p_max, scn.N0,
                           jnp.asarray(lam, jnp.float32), cfg)


def solve_plus(scn: Scenario, assign: jnp.ndarray, lam,
               cfg: SroaConfig = SroaConfig()) -> SroaResult:
    """Beyond-paper SROA+: Algorithm 4 followed by a golden-section polish
    of t*.  Guaranteed <= the paper's solution; reported separately in
    EXPERIMENTS.md so the faithful baseline stays visible."""
    cfg = dataclasses.replace(cfg, refine_iters=max(cfg.refine_iters, 32))
    return solve(scn, assign, lam, cfg)
