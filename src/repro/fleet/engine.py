"""Device-resident assignment engine: TSIA as ONE jitted computation.

The seed TSIA (:mod:`repro.core.tsia`) pays one host->device round trip per
assigning iteration; PR 1's batched TSIA (:mod:`repro.fleet.incremental`)
amortizes the neighbourhood into one round trip per iteration but still
drives the descent/escape loop from host Python.  Here the ENTIRE search —
candidate enumeration (current pattern + all N x (M-1) single moves,
mask-validated), batched SROA scoring, best-move selection, the paper's
Definition 1/2 escape, best-ever-visited tracking, and revisit-based
convergence (Remark 1) — runs inside a single ``lax.while_loop``:

* :func:`solve_assignment` — one cell's full assignment search in ONE
  jitted call (zero per-iteration host round trips);
* :func:`solve_fleet_assignments` — ``jax.vmap`` of the same loop over a
  :class:`~repro.fleet.batch.FleetScenario`, so e.g. 128 cells' complete
  searches execute as one XLA computation.

Candidate padding is fixed-size (``A = 1 + N*(M-1)`` always; moves of
masked users are flagged invalid, not dropped), so churn never changes a
shape and the engine never recompiles across dynamics events.  The search
history is recorded into fixed-size device trace buffers (see
:class:`EngineTrace`); :mod:`repro.fleet.incremental` reconstructs its
host-side ``BatchedTsiaHistory`` from them.  See DESIGN.md D7.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import sroa
from repro.core.system_model import (evaluate, evaluate_candidates,
                                     sroa_constants, sroa_constants_batched)
from repro.core.wireless import Scenario, nearest_edge_assignment
from repro.fleet.batch import (FleetScenario, candidate_assigns_device,
                               fleet_assignments)

_BIG = 1e30

# Move-kind codes in EngineTrace.moves[:, 3].
KIND_DESCENT = 0
KIND_ESCAPE = 1
KIND_COMP = 2       # compression-level change (src/dst = old/new level)


def _comp_enabled(ladder) -> bool:
    """A ladder with >= 2 rungs makes compression a decision variable;
    None or a single-rung ladder keeps the literal pre-D11 program."""
    return ladder is not None and len(ladder) >= 2


class EngineTrace(NamedTuple):
    """Fixed-size device-side search trace (one row per assigning round).

    Rows past the executed round count have ``rounds_valid == False``.
    ``moves`` rows are (user, src_edge, dst_edge, kind, moved): ``moved``
    is 0 on the final round when neither an improving move nor an escape
    existed (the round that establishes convergence scores the
    neighbourhood but stays put).
    """

    R_best: jnp.ndarray        # (T,) f32 best-ever evaluate-R after round
    R_current: jnp.ndarray     # (T,) f32 evaluate-R of the round's pattern
    moves: jnp.ndarray         # (T, 5) i32 (user, src, dst, kind, moved)
    rounds_valid: jnp.ndarray  # (T,) bool — row corresponds to a real round


class EngineResult(NamedTuple):
    assign: jnp.ndarray     # (N,) i32 best pattern ever visited
    R: jnp.ndarray          # () f32 evaluate-R (eq 15) of ``assign``
    sroa: sroa.SroaResult   # SROA allocation for ``assign``
    rounds: jnp.ndarray     # () i32 assigning iterations executed
    escapes: jnp.ndarray    # () i32 Definition-1/2 escapes taken
    converged: jnp.ndarray  # () bool — stopped by revisit/exhaustion,
    #                              not by the round cap
    trace: EngineTrace
    R_search: jnp.ndarray   # () f32 objective the search minimized: equal
    #                              to ``R`` for snapshot searches, the
    #                              time-expanded sum + switching cost for
    #                              horizon searches (DESIGN.md D10)
    comp: jnp.ndarray       # (N,) i32 per-user compression level chosen
    #                              (all zeros when the ladder is off, D11)


class _EngineState(NamedTuple):
    current: jnp.ndarray      # (N,) i32
    visited: jnp.ndarray      # (T+1, N) i32, -1 rows unused (Remark 1 set)
    best_assign: jnp.ndarray  # (N,) i32
    best_R: jnp.ndarray       # () f32
    rounds: jnp.ndarray       # () i32
    escapes: jnp.ndarray      # () i32
    done: jnp.ndarray         # () bool
    converged: jnp.ndarray    # () bool
    trace: EngineTrace


def escape_move(assign: jnp.ndarray, R_m: jnp.ndarray, b: jnp.ndarray,
                mask: jnp.ndarray, M: int,
                edge_mask: jnp.ndarray | None = None):
    """The paper's Definition 1/2 escape, as pure device arithmetic.

    Costly edge m+ = argmax R_m over *occupied* edges (Definition 1),
    economic edge m- = argmin R_m, costly user = argmax b_n among the
    movable members of m+ (Definition 2).  With an ``edge_mask`` (D12)
    m- only ranges over OPEN sites — the escape never parks a user on a
    closed edge; all-open masks leave the argmin input untouched.

    Returns (user, m_plus, m_minus, ok): ``ok`` is False when the move is
    undefined (m+ == m-, or m+ has no movable member), matching the seed
    TSIA's break conditions.
    """
    psi = jax.nn.one_hot(assign, M, dtype=jnp.float32)
    psi = psi * mask.astype(jnp.float32)[:, None]
    counts = psi.sum(axis=0)                               # (M,)
    R_m_occ = jnp.where(counts > 0, R_m, -jnp.inf)
    m_plus = jnp.argmax(R_m_occ).astype(jnp.int32)
    R_m_open = (R_m if edge_mask is None
                else jnp.where(edge_mask, R_m, jnp.inf))
    m_minus = jnp.argmin(R_m_open).astype(jnp.int32)
    member = (assign == m_plus) & mask
    user = jnp.argmax(jnp.where(member, b, -jnp.inf)).astype(jnp.int32)
    ok = (m_plus != m_minus) & (counts[m_plus] > 0) & jnp.any(member)
    return user, m_plus, m_minus, ok


@functools.lru_cache(maxsize=None)
def _topk_moves_nd(k: int):
    """Top-k pruning with a vmap rule that keeps flattening under vmap.

    Same recursion trick as ``sroa._pallas_invert_nd``: the fleet's cell
    axis (and any axis above it) broadcasts unbatched operands and
    re-enters the same custom-vmap function one rank higher, so the whole
    stacked fleet's move scoring is ONE kernel launch per round.
    """
    from jax.custom_batching import custom_vmap

    from repro.kernels import ops as kops

    @custom_vmap
    def topk_nd(gain, H, p_max, assign, mask, N0, B):
        return kops.topk_move_scores(gain, H, p_max, assign, mask, N0, B,
                                     k=k)

    @topk_nd.def_vmap
    def _rule(axis_size, in_batched, *args):  # noqa: ANN001
        args = tuple(
            a if ab else jnp.broadcast_to(a, (axis_size,) + jnp.shape(a))
            for a, ab in zip(args, in_batched))
        out = topk_nd(*args)
        return out, tuple(True for _ in out)

    return topk_nd


def _move_H(scn: Scenario, comp: jnp.ndarray | None = None,
            ladder=None) -> jnp.ndarray:
    """(N,) per-user on-wire bits the move-score kernel prices (D11).

    Tier size multipliers always apply (all-ones is bitwise the old scalar
    broadcast); an active ladder further shrinks each user's payload by
    the bytes factor of their current compression level.
    """
    H = jnp.asarray(scn.s_bits * scn.size_mult, jnp.float32)
    if comp is not None and ladder is not None:
        bf = jnp.asarray(ladder.bytes_factors(), jnp.float32)
        H = H * bf[jnp.clip(comp, 0, len(ladder) - 1)]
    return H


def _pruned_candidates(scn: Scenario, current: jnp.ndarray,
                       mask: jnp.ndarray, top_k: int):
    """The k+1 candidate patterns the move-score kernel nominates.

    Row 0 is the current pattern (so argmin ties, best-ever tracking and
    the escape's R_m[0]/b[0] reads keep their full-path meaning); rows
    1..k apply the k cheapest moves by the kernel's marginal-cost
    estimate.  Padding rows (score >= _BIG/2: fewer than k valid moves
    existed) and moves landing on a closed site (D12) are flagged
    invalid, mirroring ``candidate_assigns_device``.
    """
    user, dst, score = _topk_moves_nd(top_k)(
        scn.gain, _move_H(scn), scn.p_max, current, mask,
        jnp.asarray(scn.N0, jnp.float32),
        jnp.asarray(scn.B_open, jnp.float32))
    rows = jax.vmap(lambda u, d: current.at[u].set(d))(user, dst)
    cands = jnp.concatenate([current[None, :], rows], axis=0)
    move_ok = score < _BIG / 2
    if scn.edge_mask is not None:
        move_ok = move_ok & scn.edge_mask[dst]
    valid = jnp.concatenate([jnp.ones((1,), bool), move_ok])
    return cands, valid


def _comp_candidates(current: jnp.ndarray, comp: jnp.ndarray, M: int,
                     n_levels: int, mask: jnp.ndarray,
                     edge_mask: jnp.ndarray | None = None):
    """Full joint neighbourhood over (assignment, compression) moves.

    Assignment single-moves keep each user's compression level; the extra
    ``N * (n_levels - 1)`` rows change ONE user's level (cyclically, so
    every alternative rung is reachable in one move) while the assignment
    stays put.  Fixed-size like ``candidate_assigns_device`` — masked
    users' rows (and moves onto closed sites, D12) are flagged invalid,
    never dropped.
    """
    a_cands, a_valid = candidate_assigns_device(current, M, mask, edge_mask)
    comps_a = jnp.broadcast_to(comp, a_cands.shape)
    N = current.shape[0]
    users = jnp.repeat(jnp.arange(N, dtype=jnp.int32), n_levels - 1)
    offs = jnp.tile(jnp.arange(1, n_levels, dtype=jnp.int32), N)
    new_lv = (comp[users] + offs) % n_levels
    comps_c = jax.vmap(lambda u, lv: comp.at[u].set(lv))(users, new_lv)
    cands_c = jnp.broadcast_to(current, (N * (n_levels - 1), N))
    cands = jnp.concatenate([a_cands, cands_c], axis=0)
    comps = jnp.concatenate([comps_a, comps_c], axis=0)
    valid = jnp.concatenate([a_valid, mask[users]], axis=0)
    return cands, comps, valid


def _pruned_candidates_comp(scn: Scenario, current: jnp.ndarray,
                            comp: jnp.ndarray, mask: jnp.ndarray,
                            top_k: int, ladder):
    """Kernel-nominated joint (move, compression) candidates: 1 + 5k rows.

    The top-k kernel — fed the comp-aware per-user upload bits — nominates
    k cheap reassignments; each composes with a compression bump/drop of
    the moved user, and the same user's bump/drop without moving also
    enters (so pure compression descents need no reassignment).  Rows
    whose level leaves the ladder, or whose kernel score is padding, are
    flagged invalid.
    """
    n_levels = len(ladder)
    user, dst, score = _topk_moves_nd(top_k)(
        scn.gain, _move_H(scn, comp, ladder), scn.p_max, current, mask,
        jnp.asarray(scn.N0, jnp.float32),
        jnp.asarray(scn.B_open, jnp.float32))
    move_ok = score < _BIG / 2
    if scn.edge_mask is not None:
        move_ok = move_ok & scn.edge_mask[dst]
    rows = jax.vmap(lambda u, d: current.at[u].set(d))(user, dst)
    lv = comp[user]
    bump = jax.vmap(lambda u, l: comp.at[u].set(l))(user, lv + 1)
    drop = jax.vmap(lambda u, l: comp.at[u].set(l))(user, lv - 1)
    same = jnp.broadcast_to(current, rows.shape)
    comp0 = jnp.broadcast_to(comp, rows.shape)
    bump_ok = (lv + 1 < n_levels) & mask[user]
    drop_ok = (lv - 1 >= 0) & mask[user]
    cands = jnp.concatenate([current[None, :], rows, rows, rows,
                             same, same], axis=0)
    comps = jnp.concatenate([comp[None, :], comp0, bump, drop,
                             bump, drop], axis=0)
    valid = jnp.concatenate([jnp.ones((1,), bool), move_ok,
                             move_ok & bump_ok, move_ok & drop_ok,
                             bump_ok, drop_ok], axis=0)
    return cands, comps, valid


def _score_neighbourhood(scn: Scenario, cands: jnp.ndarray,
                         mask: jnp.ndarray, lam, cfg: sroa.SroaConfig,
                         comps: jnp.ndarray | None = None, ladder=None):
    """Batched SROA + cost model over the candidate axis (one computation).

    ``comps`` (A, N) per-candidate compression levels price each row's
    true compute/comm load through the ladder (D11); None keeps the
    literal pre-D11 scoring.
    """
    consts = sroa_constants_batched(scn, cands, mask, comps, ladder)
    B = scn.B_open

    def one(c):
        return sroa.solve_constants_impl(c, B, B, scn.f_max, scn.p_max,
                                         scn.N0, lam, cfg)

    res = jax.vmap(one)(consts)
    ev = evaluate_candidates(scn, cands, res.b, res.f, res.p, lam, mask,
                             comps, ladder)
    return res, ev


def switch_counts(cands: jnp.ndarray, incumbent: jnp.ndarray,
                  mask: jnp.ndarray) -> jnp.ndarray:
    """(A,) handovers each candidate pattern costs vs the incumbent plan.

    A handover is an ACTIVE user whose edge differs from the deployed
    (incumbent) assignment — each one pays the model re-upload, however
    many descent rounds produced the final pattern (the cost attaches to
    deploying the plan, not to the search path that found it).
    """
    diff = (cands != incumbent[None, :]) & mask[None, :]
    return diff.sum(axis=1).astype(jnp.float32)


def _score_horizon(scn: Scenario, gain_stack: jnp.ndarray,
                   cands: jnp.ndarray, mask: jnp.ndarray, lam,
                   cfg: sroa.SroaConfig, incumbent: jnp.ndarray,
                   switch_cost: float,
                   comps: jnp.ndarray | None = None, ladder=None):
    """Time-expanded scoring: every candidate against all K predicted slots.

    The horizon objective per candidate is

        R_h = sum_k R(cand; gain_k)  +  switch_cost * handovers(cand)

    — the cumulative eq-15 cost over the predicted window plus a one-time
    switching charge per user moved off the incumbent assignment.  Returns
    the slot-0 (current channel) SROA/evaluation — the escape heuristic
    and best-ever bookkeeping read those exactly as on the snapshot path —
    plus the (A,) horizon objective that drives descent.  K == 1 skips
    the slot vmap entirely, so a horizon-1 stack whose slot 0 is the live
    gain scores BIT-IDENTICALLY to the snapshot path (the parity the
    tier-1 tests pin).
    """
    K = gain_stack.shape[0]
    n_sw = switch_counts(cands, incumbent, mask)
    if K == 1:
        res, ev = _score_neighbourhood(scn._replace(gain=gain_stack[0]),
                                       cands, mask, lam, cfg, comps, ladder)
        return res, ev, ev.R + switch_cost * n_sw

    def one_slot(g):
        return _score_neighbourhood(scn._replace(gain=g), cands, mask,
                                    lam, cfg, comps, ladder)

    res_k, ev_k = jax.vmap(one_slot)(gain_stack)
    res0 = jax.tree.map(lambda x: x[0], res_k)
    ev0 = jax.tree.map(lambda x: x[0], ev_k)
    return res0, ev0, ev_k.R.sum(axis=0) + switch_cost * n_sw


def engine_core(scn: Scenario, init_assign: jnp.ndarray, mask: jnp.ndarray,
                lam, cfg: sroa.SroaConfig, max_rounds: int,
                escape_iters: int, top_k: int = 0,
                gain_stack: jnp.ndarray | None = None,
                switch_cost: float = 0.0,
                incumbent: jnp.ndarray | None = None,
                ladder=None,
                init_comp: jnp.ndarray | None = None) -> EngineResult:
    """The traceable search loop (vmap this for fleets; jit it via
    :func:`solve_assignment`).

    ``top_k > 0`` switches candidate enumeration from the full
    ``1 + N*(M-1)`` neighbourhood to the k moves nominated by the Pallas
    move-score kernel (D9): each round then runs k+1 full SROA solves
    instead of O(N*M), making the round's scoring cost independent of the
    neighbourhood size.  Descent, escape, best-ever tracking and Remark-1
    convergence are unchanged — only which moves get scored.

    ``gain_stack`` (K, N, M) switches scoring to the time-expanded horizon
    objective (D10): each candidate is SROA-scored against every predicted
    slot and charged ``switch_cost`` per active user moved off the
    ``incumbent`` (deployed) assignment, so the descent minimizes the
    cumulative cost of the predicted window plus the handover bill.  The
    loop machinery is untouched — only the per-candidate score widens.
    Move nomination (``top_k``) and the Definition-1/2 escape stay on the
    current (slot-0) channel.  ``incumbent`` defaults to ``init_assign``.

    A ``ladder`` with >= 2 rungs (D11) makes per-user compression a joint
    decision variable: the search walks (assignment, comp) pairs via
    :func:`_engine_core_comp`.  None / single-rung dispatches to the
    literal pre-D11 loop below (``comp`` comes back all-zeros), so the
    compression-off program — and its outputs — are bitwise unchanged.
    """
    if _comp_enabled(ladder):
        return _engine_core_comp(scn, init_assign, mask, lam, cfg,
                                 max_rounds, escape_iters, top_k,
                                 gain_stack, switch_cost, incumbent,
                                 ladder, init_comp)
    N, M = scn.N, scn.M
    T = int(max_rounds)
    lam = jnp.asarray(lam, jnp.float32)
    init = jnp.asarray(init_assign, jnp.int32)
    mask = jnp.asarray(mask, bool)
    em = scn.edge_mask
    if em is not None:
        # Re-home init entries sitting on a closed site (D12).  All-open
        # masks make the select the identity, keeping the fixed-M path
        # bitwise.
        init = jnp.where(em[init], init, jnp.argmax(em).astype(jnp.int32))
    horizon_mode = gain_stack is not None
    if horizon_mode:
        incumbent = init if incumbent is None else jnp.asarray(incumbent,
                                                               jnp.int32)
        switch_cost = float(switch_cost)

    def body(st: _EngineState) -> _EngineState:
        with jax.named_scope("engine.nominate"):
            if top_k > 0:
                cands, valid = _pruned_candidates(scn, st.current, mask, top_k)
            else:
                cands, valid = candidate_assigns_device(st.current, M, mask, em)
        with jax.named_scope("engine.score"):
            if horizon_mode:
                res, ev, R_score = _score_horizon(scn, gain_stack, cands,
                                                  mask, lam, cfg, incumbent,
                                                  switch_cost)
            else:
                res, ev = _score_neighbourhood(scn, cands, mask, lam, cfg)
                R_score = ev.R
        with jax.named_scope("engine.select"):
            return select(st, cands, valid, R_score, res, ev)

    def select(st, cands, valid, R_score, res, ev) -> _EngineState:
        Rv = jnp.where(valid, R_score, _BIG)
        j = jnp.argmin(Rv)                 # first minimum; index 0 on ties
        R0 = Rv[0]
        improving = Rv[j] < R0

        new_best = Rv[j] < st.best_R       # Alg 5 lines 19-21, vectorized
        best_R = jnp.where(new_best, Rv[j], st.best_R)
        best_assign = jnp.where(new_best, cands[j], st.best_assign)

        # Decode the descending move (meaningful only when improving).
        diff = cands[j] != st.current
        d_user = jnp.argmax(diff).astype(jnp.int32)
        d_src = st.current[d_user]
        d_dst = cands[j][d_user]

        # Paper-style escape at a local optimum (Definitions 1/2).
        e_user, m_plus, m_minus, e_ok = escape_move(
            st.current, ev.R_m[0], res.b[0], mask, M, em)
        can_escape = (~improving) & e_ok & (st.escapes < escape_iters)
        esc_assign = st.current.at[e_user].set(m_minus)

        moved = improving | can_escape
        nxt = jnp.where(improving, cands[j],
                        jnp.where(can_escape, esc_assign, st.current))
        # Remark 1: a revisited pattern implies a cycle (the walk is a
        # deterministic function of the pattern alone) -> converged.
        revisit = moved & jnp.any(
            jnp.all(st.visited == nxt[None, :], axis=1))
        visited = st.visited.at[st.rounds + 1].set(
            jnp.where(moved, nxt, -1))
        done = (~moved) | revisit

        r = st.rounds
        user = jnp.where(improving, d_user, e_user)
        src = jnp.where(improving, d_src, m_plus)
        dst = jnp.where(improving, d_dst, m_minus)
        kind = jnp.where(improving, KIND_DESCENT, KIND_ESCAPE)
        move_row = jnp.stack([user, src, dst, kind,
                              moved.astype(jnp.int32)]).astype(jnp.int32)
        trace = EngineTrace(
            R_best=st.trace.R_best.at[r].set(best_R),
            R_current=st.trace.R_current.at[r].set(R0),
            moves=st.trace.moves.at[r].set(move_row),
            rounds_valid=st.trace.rounds_valid.at[r].set(True))

        return _EngineState(
            current=nxt, visited=visited, best_assign=best_assign,
            best_R=best_R, rounds=r + jnp.int32(1),
            escapes=st.escapes + can_escape.astype(jnp.int32),
            done=done, converged=st.converged | done, trace=trace)

    def cond(st: _EngineState):
        return (~st.done) & (st.rounds < T)

    trace0 = EngineTrace(
        R_best=jnp.full((T,), jnp.inf, jnp.float32),
        R_current=jnp.full((T,), jnp.inf, jnp.float32),
        moves=jnp.zeros((T, 5), jnp.int32),
        rounds_valid=jnp.zeros((T,), bool))
    st0 = _EngineState(
        current=init,
        visited=jnp.full((T + 1, N), -1, jnp.int32).at[0].set(init),
        best_assign=init,
        best_R=jnp.asarray(jnp.inf, jnp.float32),
        rounds=jnp.int32(0), escapes=jnp.int32(0),
        done=jnp.asarray(False), converged=jnp.asarray(False),
        trace=trace0)
    st = lax.while_loop(cond, body, st0) if T > 0 else st0

    # One final constants-space solve for the winning pattern (also covers
    # max_rounds == 0, where the loop never scored anything).
    with jax.named_scope("engine.final"):
        B = scn.B_open
        consts = sroa_constants(scn, st.best_assign, mask)
        res = sroa.solve_constants_impl(consts, B, B, scn.f_max, scn.p_max,
                                        scn.N0, lam, cfg)
        ev = evaluate(scn, st.best_assign, res.b, res.f, res.p, lam, mask)
    # R stays the CURRENT-slot eq-15 cost of the winning pattern (what the
    # data plane reprices); R_search is the objective the descent actually
    # minimized, which the horizon path needs to compare restarts.
    return EngineResult(assign=st.best_assign, R=ev.R, sroa=res,
                        rounds=st.rounds, escapes=st.escapes,
                        converged=st.converged, trace=st.trace,
                        R_search=st.best_R if horizon_mode else ev.R,
                        comp=jnp.zeros_like(init))


class _EngineStateComp(NamedTuple):
    current: jnp.ndarray       # (N,) i32 assignment
    comp: jnp.ndarray          # (N,) i32 compression level per user
    visited: jnp.ndarray       # (T+1, N) i32 assignments, -1 rows unused
    visited_comp: jnp.ndarray  # (T+1, N) i32 comp levels of visited rows
    best_assign: jnp.ndarray   # (N,) i32
    best_comp: jnp.ndarray     # (N,) i32
    best_R: jnp.ndarray        # () f32
    rounds: jnp.ndarray        # () i32
    escapes: jnp.ndarray       # () i32
    done: jnp.ndarray          # () bool
    converged: jnp.ndarray     # () bool
    trace: EngineTrace


def _engine_core_comp(scn: Scenario, init_assign: jnp.ndarray,
                      mask: jnp.ndarray, lam, cfg: sroa.SroaConfig,
                      max_rounds: int, escape_iters: int, top_k: int = 0,
                      gain_stack: jnp.ndarray | None = None,
                      switch_cost: float = 0.0,
                      incumbent: jnp.ndarray | None = None,
                      ladder=None,
                      init_comp: jnp.ndarray | None = None) -> EngineResult:
    """Joint (assignment, compression) search loop (D11).

    Same descent/escape/best-ever/Remark-1 machinery as the pre-D11 loop,
    but the walk state is the PAIR (assignment, comp): candidates couple
    reassignment with compression bumps/drops (full neighbourhood via
    :func:`_comp_candidates`, pruned via :func:`_pruned_candidates_comp`),
    scoring prices each row through the ladder, revisit detection matches
    on both halves, and the Definition-1/2 escape moves a user while
    keeping every compression level (the escape is an assignment-space
    device; comp descents recover on the next rounds).  Trace rows for
    compression-only moves carry ``KIND_COMP`` with src/dst = old/new
    level.
    """
    N, M = scn.N, scn.M
    n_levels = len(ladder)
    T = int(max_rounds)
    lam = jnp.asarray(lam, jnp.float32)
    init = jnp.asarray(init_assign, jnp.int32)
    comp0 = (jnp.zeros_like(init) if init_comp is None
             else jnp.asarray(init_comp, jnp.int32))
    mask = jnp.asarray(mask, bool)
    em = scn.edge_mask
    if em is not None:
        init = jnp.where(em[init], init, jnp.argmax(em).astype(jnp.int32))
    horizon_mode = gain_stack is not None
    if horizon_mode:
        incumbent = init if incumbent is None else jnp.asarray(incumbent,
                                                               jnp.int32)
        switch_cost = float(switch_cost)

    def body(st: _EngineStateComp) -> _EngineStateComp:
        with jax.named_scope("engine.nominate"):
            if top_k > 0:
                cands, comps, valid = _pruned_candidates_comp(
                    scn, st.current, st.comp, mask, top_k, ladder)
            else:
                cands, comps, valid = _comp_candidates(
                    st.current, st.comp, M, n_levels, mask, em)
        with jax.named_scope("engine.score"):
            if horizon_mode:
                res, ev, R_score = _score_horizon(scn, gain_stack, cands,
                                                  mask, lam, cfg, incumbent,
                                                  switch_cost, comps, ladder)
            else:
                res, ev = _score_neighbourhood(scn, cands, mask, lam, cfg,
                                               comps, ladder)
                R_score = ev.R
        with jax.named_scope("engine.select"):
            return select(st, cands, comps, valid, R_score, res, ev)

    def select(st, cands, comps, valid, R_score, res, ev) -> _EngineStateComp:
        Rv = jnp.where(valid, R_score, _BIG)
        j = jnp.argmin(Rv)                 # first minimum; index 0 on ties
        R0 = Rv[0]
        improving = Rv[j] < R0

        new_best = Rv[j] < st.best_R
        best_R = jnp.where(new_best, Rv[j], st.best_R)
        best_assign = jnp.where(new_best, cands[j], st.best_assign)
        best_comp = jnp.where(new_best, comps[j], st.best_comp)

        # Decode the move for the trace: the assignment half when the
        # user moved edges, else the compression half.
        a_diff = cands[j] != st.current
        c_diff = comps[j] != st.comp
        a_moved = jnp.any(a_diff)
        d_user = jnp.where(a_moved, jnp.argmax(a_diff),
                           jnp.argmax(c_diff)).astype(jnp.int32)
        d_src = jnp.where(a_moved, st.current[d_user], st.comp[d_user])
        d_dst = jnp.where(a_moved, cands[j][d_user], comps[j][d_user])
        d_kind = jnp.where(a_moved, KIND_DESCENT, KIND_COMP)

        e_user, m_plus, m_minus, e_ok = escape_move(
            st.current, ev.R_m[0], res.b[0], mask, M, em)
        can_escape = (~improving) & e_ok & (st.escapes < escape_iters)
        esc_assign = st.current.at[e_user].set(m_minus)

        moved = improving | can_escape
        nxt = jnp.where(improving, cands[j],
                        jnp.where(can_escape, esc_assign, st.current))
        nxt_comp = jnp.where(improving, comps[j], st.comp)
        revisit = moved & jnp.any(
            jnp.all(st.visited == nxt[None, :], axis=1)
            & jnp.all(st.visited_comp == nxt_comp[None, :], axis=1))
        visited = st.visited.at[st.rounds + 1].set(
            jnp.where(moved, nxt, -1))
        visited_comp = st.visited_comp.at[st.rounds + 1].set(
            jnp.where(moved, nxt_comp, -1))
        done = (~moved) | revisit

        r = st.rounds
        user = jnp.where(improving, d_user, e_user)
        src = jnp.where(improving, d_src, m_plus)
        dst = jnp.where(improving, d_dst, m_minus)
        kind = jnp.where(improving, d_kind, KIND_ESCAPE)
        move_row = jnp.stack([user, src, dst, kind,
                              moved.astype(jnp.int32)]).astype(jnp.int32)
        trace = EngineTrace(
            R_best=st.trace.R_best.at[r].set(best_R),
            R_current=st.trace.R_current.at[r].set(R0),
            moves=st.trace.moves.at[r].set(move_row),
            rounds_valid=st.trace.rounds_valid.at[r].set(True))

        return _EngineStateComp(
            current=nxt, comp=nxt_comp, visited=visited,
            visited_comp=visited_comp, best_assign=best_assign,
            best_comp=best_comp, best_R=best_R,
            rounds=r + jnp.int32(1),
            escapes=st.escapes + can_escape.astype(jnp.int32),
            done=done, converged=st.converged | done, trace=trace)

    def cond(st: _EngineStateComp):
        return (~st.done) & (st.rounds < T)

    trace0 = EngineTrace(
        R_best=jnp.full((T,), jnp.inf, jnp.float32),
        R_current=jnp.full((T,), jnp.inf, jnp.float32),
        moves=jnp.zeros((T, 5), jnp.int32),
        rounds_valid=jnp.zeros((T,), bool))
    st0 = _EngineStateComp(
        current=init, comp=comp0,
        visited=jnp.full((T + 1, N), -1, jnp.int32).at[0].set(init),
        visited_comp=jnp.full((T + 1, N), -1, jnp.int32).at[0].set(comp0),
        best_assign=init, best_comp=comp0,
        best_R=jnp.asarray(jnp.inf, jnp.float32),
        rounds=jnp.int32(0), escapes=jnp.int32(0),
        done=jnp.asarray(False), converged=jnp.asarray(False),
        trace=trace0)
    st = lax.while_loop(cond, body, st0) if T > 0 else st0

    with jax.named_scope("engine.final"):
        B = scn.B_open
        consts = sroa_constants(scn, st.best_assign, mask, st.best_comp,
                                ladder)
        res = sroa.solve_constants_impl(consts, B, B, scn.f_max, scn.p_max,
                                        scn.N0, lam, cfg)
        ev = evaluate(scn, st.best_assign, res.b, res.f, res.p, lam, mask,
                      st.best_comp, ladder)
    return EngineResult(assign=st.best_assign, R=ev.R, sroa=res,
                        rounds=st.rounds, escapes=st.escapes,
                        converged=st.converged, trace=st.trace,
                        R_search=st.best_R if horizon_mode else ev.R,
                        comp=st.best_comp)


def _start_patterns(scn: Scenario, init: jnp.ndarray, mask: jnp.ndarray,
                    n_starts: int,
                    tail: jnp.ndarray | None = None) -> jnp.ndarray:
    """(S, N) initial patterns for multi-start search (D9).

    Start 0 is the caller's pattern (so best-of-starts can never be worse
    than the single-start search), start 1 the best-gain greedy pattern,
    and further starts deterministic pseudo-random draws (fixed key — the
    engine stays a pure function of its arguments).  Masked users keep
    their init value in every start; the engine never moves them.

    With an ``edge_mask`` (D12) the greedy start ranks gains over OPEN
    sites only and random draws landing on a closed site re-home to the
    first open one; all-open masks leave every pattern untouched.

    ``tail`` appends ONE extra start row — the receding-horizon warm
    start (D10): the previous window's winning pattern.  Because it is an
    additional restart on top of the cold start set, warm-started search
    is structurally never worse than cold (argmin over a superset).
    """
    em = scn.edge_mask
    inits = [init]
    if n_starts > 1:
        g = (scn.gain if em is None
             else jnp.where(em[None, :], scn.gain, -jnp.inf))
        greedy = jnp.argmax(g, axis=1).astype(jnp.int32)
        inits.append(jnp.where(mask, greedy, init))
    for s in range(2, n_starts):
        key = jax.random.fold_in(jax.random.PRNGKey(17), s)
        rnd = jax.random.randint(key, init.shape, 0, scn.M, jnp.int32)
        if em is not None:
            rnd = jnp.where(em[rnd], rnd, jnp.argmax(em).astype(jnp.int32))
        inits.append(jnp.where(mask, rnd, init))
    if tail is not None:
        inits.append(jnp.where(mask, jnp.asarray(tail, jnp.int32), init))
    return jnp.stack(inits, axis=0)


def search_core(scn: Scenario, init_assign: jnp.ndarray, mask: jnp.ndarray,
                lam, cfg: sroa.SroaConfig, max_rounds: int,
                escape_iters: int, top_k: int = 0,
                n_starts: int = 1,
                gain_stack: jnp.ndarray | None = None,
                switch_cost: float = 0.0,
                incumbent: jnp.ndarray | None = None,
                ladder=None,
                init_comp: jnp.ndarray | None = None,
                tail_init: jnp.ndarray | None = None) -> EngineResult:
    """Multi-start wrapper around :func:`engine_core` (still traceable).

    ``n_starts > 1`` vmaps the whole search loop over distinct initial
    patterns — one extra batch axis on the existing loop state, so the S
    restarts run as one batched computation — and returns the restart
    whose final evaluate-R is best.  Because start 0 is the caller's init,
    the result is never worse than the single-start search with the same
    knobs (the property the tier-1 guard tests assert).

    On the horizon path the incumbent assignment is shared by every
    restart (the switching bill is against the DEPLOYED plan, whatever
    pattern a restart explores from) and the winner is chosen by the
    horizon objective (``R_search``), not the current-slot R.

    ``tail_init`` adds one more restart row — the receding-horizon warm
    start (the previous window's winning pattern, stashed by the service).
    Its presence can only grow the start set, so warm never loses to cold.
    """
    if gain_stack is not None and incumbent is None:
        incumbent = jnp.asarray(init_assign, jnp.int32)
    if n_starts <= 1 and tail_init is None:
        return engine_core(scn, init_assign, mask, lam, cfg, max_rounds,
                           escape_iters, top_k, gain_stack, switch_cost,
                           incumbent, ladder, init_comp)
    init = jnp.asarray(init_assign, jnp.int32)
    inits = _start_patterns(scn, init, jnp.asarray(mask, bool), n_starts,
                            tail_init)

    def one(ia):
        # Every restart explores compression from the caller's init levels
        # (start 0 = caller's assignment too, so the never-worse property
        # holds for the joint search as well).
        return engine_core(scn, ia, mask, lam, cfg, max_rounds,
                           escape_iters, top_k, gain_stack, switch_cost,
                           incumbent, ladder, init_comp)

    res = jax.vmap(one)(inits)
    i = jnp.argmin(res.R_search if gain_stack is not None else res.R)
    return jax.tree.map(lambda x: x[i], res)


def inversion_batches(rows: int, N: int, M: int, top_k: int = 0,
                      n_starts: int = 1, tail: bool = False,
                      horizon: int = 1, ladder=None) -> list[tuple]:
    """Batch shapes of SROA's inversions in one fleet engine call over
    ``rows`` cells (see ``sroa.invert_tiles``): the scoring's
    (rows, [starts], [slots], A, N), A the candidates a round scores, and
    the final solve's (rows, [starts], N)."""
    levels = len(ladder) if _comp_enabled(ladder) else 1
    if top_k > 0:
        A = 1 + (5 if levels > 1 else 1) * top_k
    else:
        A = 1 + N * (M - 1) + N * (levels - 1)
    lead = (rows,)
    if n_starts > 1 or tail:
        lead += (max(n_starts, 1) + int(tail),)
    slots = (horizon,) if horizon > 1 else ()
    return [lead + slots + (A, N), lead + (N,)]


@partial(jax.jit, static_argnames=("cfg", "max_rounds", "escape_iters",
                                   "top_k", "n_starts", "switch_cost",
                                   "ladder"))
def solve_assignment(scn: Scenario, init_assign: jnp.ndarray | None = None,
                     mask: jnp.ndarray | None = None, lam=1.0,
                     cfg: sroa.SroaConfig = sroa.SroaConfig(),
                     max_rounds: int = 48,
                     escape_iters: int = 6, top_k: int = 0,
                     n_starts: int = 1,
                     gain_stack: jnp.ndarray | None = None,
                     switch_cost: float = 0.0,
                     incumbent: jnp.ndarray | None = None,
                     ladder=None,
                     init_comp: jnp.ndarray | None = None,
                     tail_init: jnp.ndarray | None = None) -> EngineResult:
    """One cell's ENTIRE assignment search as one jitted call.

    Args:
      scn:          wireless scenario (pytree of arrays).
      init_assign:  (N,) int32 start pattern (nearest-edge when None,
                    Alg 5 line 5).
      mask:         (N,) bool active users (None = all active); inactive
                    users are never moved and carry zero cost.
      lam:          objective weight lambda (eq 15).
      cfg:          SROA config shared by every candidate solve.
      max_rounds:   assigning-iteration cap (sizes the trace buffers).
      escape_iters: non-improving Definition-1/2 escapes allowed.
      top_k:        0 = score the full 1 + N*(M-1) neighbourhood per
                    round; > 0 = score only the k kernel-nominated moves
                    (sub-quadratic rounds, see D9).
      n_starts:     parallel restarts from distinct initial patterns;
                    best final objective wins (never worse than 1).
      gain_stack:   optional (K, N, M) predicted-gain stack (slot 0 = the
                    current channel): switches to the time-expanded
                    horizon objective (D10).
      switch_cost:  per-handover charge (weighted cost units) against the
                    incumbent assignment; static — one compile per value.
      incumbent:    (N,) deployed assignment handovers are billed against
                    (defaults to ``init_assign``).
      ladder:       CompressionLadder (static, hashable); >= 2 rungs makes
                    per-user compression a joint decision variable (D11).
                    None / 1 rung keeps the literal pre-D11 program.
      init_comp:    (N,) i32 starting compression levels (zeros when
                    None — i.e. every user uncompressed).
      tail_init:    (N,) i32 receding-horizon warm-start pattern (the
                    previous window's winner); joins the restart set as
                    one extra row, so warm search never loses to cold.
    """
    if mask is None:
        mask = jnp.ones((scn.N,), bool)
    if init_assign is None:
        init_assign = nearest_edge_assignment(scn)
    if gain_stack is not None and gain_stack.shape[0] == 1 \
            and switch_cost == 0.0:
        # K=1 with no switching charge IS snapshot planning: route through
        # the identical snapshot computation (slot 0 is the live channel by
        # the rollout contract) so the parity is bitwise, not approximate —
        # a differently-fused horizon program can drift by an ulp.
        scn = scn._replace(gain=jnp.asarray(gain_stack[0], scn.gain.dtype))
        gain_stack = incumbent = None
    return search_core(scn, init_assign, mask, lam, cfg, max_rounds,
                       escape_iters, top_k, n_starts, gain_stack,
                       switch_cost, incumbent, ladder, init_comp, tail_init)


@partial(jax.jit, static_argnames=("cfg", "max_rounds", "escape_iters",
                                   "top_k", "n_starts", "switch_cost",
                                   "ladder"))
def solve_fleet_assignments(fleet: FleetScenario,
                            init_assigns: jnp.ndarray | None = None,
                            lam=1.0,
                            cfg: sroa.SroaConfig = sroa.SroaConfig(),
                            max_rounds: int = 48,
                            escape_iters: int = 6, top_k: int = 0,
                            n_starts: int = 1,
                            gain_stacks: jnp.ndarray | None = None,
                            switch_cost: float = 0.0,
                            incumbents: jnp.ndarray | None = None,
                            ladder=None,
                            init_comps: jnp.ndarray | None = None,
                            tail_inits: jnp.ndarray | None = None
                            ) -> EngineResult:
    """Full assignment searches for EVERY cell of a fleet in one call.

    ``jax.vmap`` of :func:`search_core` over the stacked cells: every leaf
    of the returned :class:`EngineResult` carries a leading (C,) axis.
    ``lam`` may be scalar or (C,).  Cells that converge early idle inside
    the batched while_loop (their element-wise state is frozen) until the
    slowest cell finishes — still zero host round trips overall (see
    :func:`solve_fleet_assignments_bucketed` for the scheduling fix).
    ``gain_stacks`` (C, K, N, M) — with ``switch_cost``/``incumbents`` —
    switches every cell to the time-expanded horizon objective (D10);
    ``tail_inits`` (C, N) feeds each cell's receding-horizon warm start.

    The optional operands ride in ONE extras pytree: a ``None`` member is
    an empty subtree, so every on/off combination keeps its own treedef —
    and hence its own compiled program — without hand-written variants.
    """
    if init_assigns is None:
        init_assigns = fleet_assignments(fleet)
    lam_v = jnp.broadcast_to(jnp.asarray(lam, jnp.float32), (fleet.C,))
    init = jnp.asarray(init_assigns, jnp.int32)
    if gain_stacks is not None and gain_stacks.shape[1] == 1 \
            and switch_cost == 0.0:
        # K=1 + zero switching charge degenerates to snapshot planning:
        # use the snapshot program itself so parity is bitwise (the
        # horizon vmap fuses differently and can drift by an ulp).
        gain = jnp.asarray(gain_stacks[:, 0], fleet.cells.gain.dtype)
        fleet = fleet._replace(cells=fleet.cells._replace(gain=gain))
        gain_stacks = incumbents = None
    comp_on = _comp_enabled(ladder)
    comps = (jnp.zeros_like(init) if init_comps is None
             else jnp.asarray(init_comps, jnp.int32)) if comp_on else None
    if gain_stacks is not None:
        gain_stacks = jnp.asarray(gain_stacks, jnp.float32)
        incumbents = jnp.asarray(init if incumbents is None else incumbents,
                                 jnp.int32)
    else:
        incumbents = None
    if tail_inits is not None:
        tail_inits = jnp.asarray(tail_inits, jnp.int32)

    def one(cell, init_a, mask, l, extras):
        gs, inc, ic, tl = extras
        return search_core(cell, init_a, mask, l, cfg, max_rounds,
                           escape_iters, top_k, n_starts, gs, switch_cost,
                           inc, ladder, ic, tl)

    return jax.vmap(one)(fleet.cells, init, fleet.mask, lam_v,
                         (gain_stacks, incumbents, comps, tail_inits))


def difficulty_proxy(fleet: FleetScenario) -> jnp.ndarray:
    """(C,) convergence-difficulty proxy for bucket scheduling.

    Active-user count dominates how many assigning rounds a cell needs
    (bigger neighbourhood, longer descents); the normalized gain spread
    breaks ties — flat channels converge fast, heterogeneous ones wander.
    Cheap (no solves), monotone-ish in observed trip counts; exactness is
    not required, only a useful sort order.
    """
    m = fleet.mask.astype(jnp.float32)
    n_act = jnp.sum(m, axis=1)
    g = jnp.log(jnp.maximum(fleet.cells.gain, 1e-30))
    g_best = jnp.max(g, axis=2)
    spread = jnp.std(jnp.where(fleet.mask, g_best, 0.0), axis=1)
    return n_act + spread / jnp.maximum(jnp.max(spread), 1e-9)


def solve_fleet_assignments_bucketed(
        fleet: FleetScenario, init_assigns: jnp.ndarray | None = None,
        lam=1.0, cfg: sroa.SroaConfig = sroa.SroaConfig(),
        max_rounds: int = 48, escape_iters: int = 6, top_k: int = 0,
        n_starts: int = 1, n_buckets: int = 2, ladder=None,
        init_comps: jnp.ndarray | None = None) -> EngineResult:
    """Bucket-by-difficulty fleet scheduling (EXPERIMENTS.md §Perf item b).

    The batched engine while_loop runs every cell for the worst
    trip count of its batch: one stubborn cell drags all converged ones
    through full-cost rounds (their state is frozen, the FLOPs are not).
    Here cells are sorted by :func:`difficulty_proxy` and solved in
    ``n_buckets`` equal-size batched calls, so easy buckets exit at their
    own worst case instead of the fleet's.  Equal bucket sizes keep the
    compile count at one program per fleet-size/bucket-count pair.

    Host-side orchestration (n_buckets jitted calls instead of 1);
    results are re-scattered to the caller's cell order, so the returned
    :class:`EngineResult` is leaf-for-leaf comparable with
    :func:`solve_fleet_assignments` — same searches, same answers.
    """
    C = fleet.C
    if n_buckets <= 1 or C < 2 * n_buckets:
        return solve_fleet_assignments(fleet, init_assigns, lam, cfg,
                                       max_rounds, escape_iters, top_k,
                                       n_starts, ladder=ladder,
                                       init_comps=init_comps)
    if init_assigns is None:
        init_assigns = fleet_assignments(fleet)
    init_assigns = jnp.asarray(init_assigns, jnp.int32)
    if init_comps is not None:
        init_comps = jnp.asarray(init_comps, jnp.int32)
    lam_v = jnp.broadcast_to(jnp.asarray(lam, jnp.float32), (C,))
    order = jnp.argsort(difficulty_proxy(fleet))

    # Equal-size buckets (remainder rides with the hardest bucket) so the
    # per-bucket program is compiled once per (C, n_buckets).
    size = C // n_buckets
    parts = []
    outs = []
    for i in range(n_buckets):
        lo = i * size
        hi = lo + size if i < n_buckets - 1 else C
        idx = order[lo:hi]
        parts.append(idx)
        sub = jax.tree.map(lambda x, ix=idx: x[ix], fleet)
        outs.append(solve_fleet_assignments(
            sub, init_assigns[idx], lam_v[idx], cfg, max_rounds,
            escape_iters, top_k, n_starts, ladder=ladder,
            init_comps=None if init_comps is None else init_comps[idx]))
    perm = jnp.concatenate(parts)
    inv = jnp.argsort(perm)
    stacked = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *outs)
    return jax.tree.map(lambda x: x[inv], stacked)


def sroa_solve_flops(N: int, cfg: sroa.SroaConfig) -> int:
    """Analytic FLOP model of ONE constants-space SROA solve (worst-case
    trip counts; the accounting benchmarks/run.py --json reports).

    The nest is t_iters x (p_iters x (f_iters x (b_iters x N))): every
    bandwidth-inversion step costs ~8 flops/user, each f step adds the
    budget reduction, and `_auto_bounds` prepends t_iters more inversions.
    """
    inv = 8 * cfg.b_iters * N
    alg2 = cfg.f_iters * (inv + 12 * N)
    alg3 = cfg.p_iters * (alg2 + 8 * N)
    bounds = cfg.t_iters * (inv + 10 * N)
    return bounds + cfg.t_iters * (alg3 + 20 * N)


def candidate_search_flops(N: int, M: int, rounds: int,
                           cfg: sroa.SroaConfig, top_k: int = 0) -> dict:
    """Candidate-scoring cost of one engine search (analytic, see D9).

    Returns a dict with the per-round candidate count and total FLOPs:
    full path scores 1 + N*(M-1) candidates per round (quadratic in N
    once each solve's O(N) cost is included); the pruned path scores
    k + 1 plus the O(N*M) move-score kernel — linear in N.
    """
    solve = sroa_solve_flops(N, cfg)
    if top_k > 0:
        cands = 1 + top_k
        proxy = (12 + top_k) * N * M        # score + k knockout reductions
    else:
        cands = 1 + N * (M - 1)
        proxy = 0
    return {"cands_per_round": cands,
            "score_flops": rounds * (cands * solve + proxy)}
