"""The comparison that decides ``correct``.

Three numbers, each beside its limit (``LIMITS``):

* ``responses_wrong``: requests due in the window that were never answered,
  or whose response does not carry the plan table of its tick (exact).
* ``reprice_gap``: re-pricing layer.  For (tick, cell) pairs drawn from the
  seed, at most half of those the window made, the plain SROA
  (``bench/reference.py``) re-solves the deployed assignment under that
  tick's channel.  The number is the widest relative gap between the
  reference's R and either the served R or the R that the served b/f/p/t
  price to; an over-budget allocation reads inf.
* ``search_gap``: re-search layer.  For searches drawn from the seed, the
  reference scores the whole single-move neighbourhood at every round of
  the program's own trajectory, from the same start.  A descent step reads
  how far its chosen move lies above the reference's best move, a round
  that stops or escapes reads the improvement it left, and the deployed
  assignment reads how far it lies above the best pattern scored.  The
  number is the widest of these; a trajectory the reference cannot follow
  (a move off the current pattern, a replanned cell with no search) reads
  inf.

Where the configuration has a compression ladder and the service deploys
a level per user, both numbers price each plan at its deployed levels:
re-pricing re-solves under them, and the search's neighbourhood is the
joint one, whose compression moves (``KIND_COMP``) the replay follows as
it follows descents; escapes keep the levels.

``gather`` copies the inputs and outputs of the sampled cells to the host
while the program still holds them; ``compare`` runs the reference once the
program's state is freed.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from bench import reference as ref

# Limits, set from the program's sound runs and the bfloat16 control on the
# chip (PERF.md, "Cells"): above every sound reading, below every control
# reading, with the more room above the sound ones.  responses_wrong is exact.
LIMITS = {"responses_wrong": 0, "reprice_gap": 1e-3, "search_gap": 1e-3}
REPRICE_SAMPLES = 32
SEARCH_SAMPLES = 4
KIND_DESCENT, KIND_ESCAPE, KIND_COMP = 0, 1, 2  # the engine trace's move kinds


def _cell(cells, i: int) -> dict:
    """Row i of the program's stacked scenario, as host arrays."""
    return {k: np.asarray(getattr(cells, k)[i]) for k in ref.CELL_KEYS}


def _responses_wrong(run) -> int:
    plans = {t.plan["tick"]: t.plan for t in run.ticks}
    wrong = 0
    for r in run.requests:
        resp = r.response
        if r.done is None or resp is None or resp.get("tick") not in plans:
            wrong += 1
            continue
        plan = plans[resp["tick"]]
        if (not np.array_equal(np.asarray(resp["R"], np.float64),
                               np.asarray(plan["R"], np.float64))
                or not np.array_equal(np.asarray(resp["assign"]),
                                      plan["assign"])
                or ("comp" in plan and not np.array_equal(
                    np.asarray(resp["comp"]), plan["comp"]))):
            wrong += 1
    return wrong


def gather(run) -> dict:
    """Host copies of what the comparison needs, sampled from the seed."""
    rng = np.random.default_rng([run.seed, 0xC4EC])
    C = run.cells_C
    pairs = [(k, i) for k in range(len(run.ticks)) for i in range(C)]
    # At most half of the pairs, so that seeds check different plans.
    pick = rng.choice(len(pairs),
                      size=min(REPRICE_SAMPLES, max(1, len(pairs) // 2)),
                      replace=False)
    reprice = []
    for j in sorted(pick):
        k, i = pairs[j]
        p = run.ticks[k].plan
        reprice.append({
            "cell": _cell(p["fleet"].cells, i), "mask": p["active"][i],
            "assign": p["assign"][i], "b": p["b"][i], "f": p["f"][i],
            "p": p["p"][i], "t": p["t"][i], "R": p["R"][i],
            "lam": p["lam"]})
        if "comp" in p:
            reprice[-1]["comp"] = p["comp"][i].astype(np.int32)
    # Every replanned cell of every tick must have had a search.
    missing = 0
    cands = []
    for k, t in enumerate(run.ticks):
        found = {}
        for s in t.searches:
            rows = np.asarray(s["rows"])
            for r, cell in enumerate(rows):
                found.setdefault(int(cell), (s, r))
        missing += sum(int(i) not in found for i in t.replanned)
        for i in t.replanned:
            if int(i) in found:
                cands.append((k, int(i)) + found[int(i)])
    search = []
    if cands:
        pick = rng.choice(len(cands), size=min(SEARCH_SAMPLES, len(cands)),
                          replace=False)
        for j in sorted(pick):
            k, i, s, r = cands[j]
            out, plan = s["out"], run.ticks[k].plan
            search.append({
                "cell": _cell(s["fleet"].cells, r),
                "mask": np.asarray(s["fleet"].mask[r], bool),
                "init": np.asarray(s["init"][r], np.int32),
                "moves": np.asarray(out.trace.moves[r]),
                "valid": np.asarray(out.trace.rounds_valid[r], bool),
                "served": plan["assign"][i].astype(np.int32),
                "lam": plan["lam"]})
            if "comp" in plan:
                # The engine starts a search without levels at level 0.
                ic = s.get("init_comps")
                search[-1]["init_comp"] = (
                    np.zeros_like(search[-1]["init"]) if ic is None
                    else np.asarray(ic[r], np.int32))
                search[-1]["served_comp"] = plan["comp"][i].astype(np.int32)
    return {"reprice": reprice, "search": search, "missing": missing,
            "responses_wrong": _responses_wrong(run)}


@functools.lru_cache(maxsize=None)
def _solvers(caps: tuple, ladder: tuple | None = None):
    """The reference's programs, in the configuration's float32.  Each takes
    the per-user levels last: None prices without the ladder."""
    import jax
    import jax.numpy as jnp
    dt = jnp.float32

    def sroa_one(cell, assign, mask, lam, comp):
        return ref.sroa(ref.cast(cell, dt), assign, mask,
                        jnp.asarray(lam, dt), caps, comp, ladder)

    def claimed(cell, assign, mask, lam, b, f, p, t, comp):
        c = ref.cast(cell, dt)
        A, J, H, delta, h, E_ct = ref.constants(c, assign, mask, comp,
                                                ladder)
        G = p * h / c["N0"]
        T_com = jnp.where(b > 0, H / jnp.maximum(ref._rate(b, G), 1e-30),
                          ref.BIG)
        return jnp.sum(p * T_com + A * f ** 2) + E_ct + lam * t

    def nbhd(cell, assign, mask, lam, comp):
        return ref.score_neighbourhood(ref.cast(cell, dt), assign, mask,
                                       jnp.asarray(lam, dt), caps, comp,
                                       ladder)

    def score(cell, assign, mask, lam, comp):
        return ref.score(ref.cast(cell, dt), assign, mask,
                         jnp.asarray(lam, dt), caps, comp, ladder)

    return (jax.jit(jax.vmap(sroa_one)), jax.jit(jax.vmap(claimed)),
            jax.jit(jax.vmap(nbhd)), jax.jit(jax.vmap(score)))


def _stack(items, key):
    return np.stack([np.asarray(x[key]) for x in items])


def _stack_cells(items):
    return {k: np.stack([x["cell"][k] for x in items]) for k in ref.CELL_KEYS}


def _levels(items, key, ladder):
    """The items' per-user levels, or None where the plans carry none."""
    if key not in items[0]:
        return None
    if ladder is None:
        raise ValueError("plans carry compression levels; the comparison "
                         "needs the configuration's ladder")
    return _stack(items, key)


def reprice_gap(items, caps: tuple, ladder: tuple | None = None) -> float:
    if not items:
        return math.inf
    sroa_v, claimed_v, _, _ = _solvers(caps, ladder)
    cells = _stack_cells(items)
    assign, mask = _stack(items, "assign"), _stack(items, "mask")
    lam = _stack(items, "lam").astype(np.float32)
    comp = _levels(items, "comp", ladder)
    R_ref = np.asarray(sroa_v(cells, assign, mask, lam, comp)[4], np.float64)
    f32 = lambda k: _stack(items, k).astype(np.float32)  # noqa: E731
    R_cl = np.asarray(claimed_v(cells, assign, mask, lam, f32("b"), f32("f"),
                                f32("p"), f32("t"), comp), np.float64)
    R_sv = _stack(items, "R").astype(np.float64)
    B = cells["B_edges"].astype(np.float64).sum(axis=1)
    over = (f32("b").astype(np.float64) * mask).sum(axis=1) > B * (1 + 1e-3)
    gap = np.maximum(np.abs(R_sv - R_ref), np.abs(R_cl - R_ref)) / np.abs(R_ref)
    gap = np.where(over | ~np.isfinite(gap), np.inf, gap)
    return float(gap.max())


def search_gap(items, caps: tuple, ladder: tuple | None = None) -> float:
    """Replay each sampled search's trajectory under the reference."""
    if not items:
        return 0.0
    _, _, nbhd_v, score_v = _solvers(caps, ladder)
    cells = _stack_cells(items)
    mask = _stack(items, "mask")
    lam = _stack(items, "lam").astype(np.float32)
    N, M = cells["gain"].shape[1:]
    cur = _stack(items, "init").copy()
    lv = _levels(items, "init_comp", ladder)
    L = 1 if lv is None else len(ladder)
    live = np.ones(len(items), bool)
    gaps = np.zeros(len(items))
    best = np.full(len(items), np.inf)
    rounds = items[0]["moves"].shape[0]
    for r in range(rounds):
        for j, it in enumerate(items):
            live[j] &= bool(it["valid"][r])
        if not live.any():
            break
        _, R = nbhd_v(cells, cur, mask, lam, lv)
        R = np.asarray(R, np.float64)
        for j, it in enumerate(items):
            if not live[j]:
                continue
            lo = R[j].min()
            best[j] = min(best[j], lo)
            user, src, dst, kind, moved = (int(x) for x in it["moves"][r])
            if moved and kind in (KIND_DESCENT, KIND_COMP):
                # Off the current pattern, or a level the ladder lacks.
                state, n = (cur, M) if kind == KIND_DESCENT else (lv, L)
                if (state is None or not 0 <= user < N
                        or state[j, user] != src or src == dst
                        or not 0 <= dst < n):
                    gaps[j] = np.inf
                    live[j] = False
                    continue
                row = 1 + user * (n - 1) + ((dst - src) % n - 1)
                if kind == KIND_COMP:
                    row += N * (M - 1)
                gaps[j] = max(gaps[j], (R[j, row] - lo) / abs(lo))
                state[j, user] = dst
            else:
                gaps[j] = max(gaps[j], (R[j, 0] - lo) / abs(lo))
                if moved and kind == KIND_ESCAPE:
                    cur[j, user] = dst
                else:
                    live[j] = False
    served = _stack(items, "served")
    R_srv = np.asarray(score_v(cells, served, mask, lam,
                               _levels(items, "served_comp", ladder)),
                       np.float64)
    final = (R_srv - best) / np.abs(best)
    gaps = np.maximum(gaps, np.where(np.isfinite(best), final, np.inf))
    gaps = np.where(np.isnan(gaps), np.inf, gaps)
    return float(gaps.max())


def compare(g: dict, caps: tuple, ladder: tuple | None = None) -> dict:
    """The numbers compared, each with its limit, and the verdict.

    ``ladder`` is the configuration's, one (bytes_factor, epoch_factor)
    pair per rung; plans that carry levels are priced through it."""
    search = search_gap(g["search"], caps, ladder)
    if g["missing"]:
        search = math.inf
    nums = {"responses_wrong": g["responses_wrong"],
            "reprice_gap": reprice_gap(g["reprice"], caps, ladder),
            "search_gap": search}
    ok = all(nums[k] <= LIMITS[k] for k in nums)
    return {"correct": ok,
            "numbers": {k: {"value": nums[k], "limit": LIMITS[k]}
                        for k in nums}}
