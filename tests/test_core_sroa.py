"""Unit + property tests for the paper's cost model and SROA (Algs 2-4)."""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from _hypothesis_compat import given, settings, st

from repro.core import baselines, sroa, system_model, wireless

LAM = 1.0


@pytest.fixture(scope="module")
def scn():
    return wireless.draw_scenario(0)


@pytest.fixture(scope="module")
def assign(scn):
    return wireless.nearest_edge_assignment(scn)


@pytest.fixture(scope="module")
def sroa_res(scn, assign):
    return sroa.solve(scn, assign, LAM)


# ---------------------------------------------------------------- cost model
def test_rate_monotone_in_bandwidth(scn):
    b = jnp.linspace(1e3, 1e6, 64)
    r = system_model.rate(b, 1e-10, 0.1, scn.N0)
    assert bool(jnp.all(jnp.diff(r) > 0))


def test_rate_lemma1_upper_bound():
    """Lemma 1: b log2(1+G/b) < G/ln2 for all b."""
    G = jnp.asarray([1e3, 1e6, 1e9])
    for b in [1e2, 1e5, 1e8, 1e12]:
        vals = sroa.rate_fn(jnp.full_like(G, b), G)
        assert bool(jnp.all(vals <= (G / np.log(2.0)) * (1 + 1e-5)))


def test_evaluate_matches_hand_computation(scn, assign):
    """Cross-check eqs 4-15 against a straight numpy transcription."""
    N, M = scn.N, scn.M
    b = np.full(N, float(scn.B_total) / N)
    f = np.asarray(scn.f_max)
    p = np.asarray(scn.p_max)
    a = np.asarray(assign)
    g = np.asarray(scn.gain)[np.arange(N), a]
    L, K, I = float(scn.L), float(scn.K), float(scn.I)
    c, D = np.asarray(scn.c), np.asarray(scn.D)
    s, N0, alpha = float(scn.s_bits), float(scn.N0), float(scn.alpha)

    T_cmp = L * c * D / f
    E_cmp = 0.5 * alpha * L * f ** 2 * c * D
    r = b * np.log2(1.0 + g * p / (N0 * b))
    T_com = s / r
    E_com = p * T_com
    T_cloud = np.asarray(scn.T_cloud())
    E_cloud = np.asarray(scn.E_cloud())
    T_m = np.array([K * (T_cmp + T_com)[a == m].max() if (a == m).any() else 0.0
                    for m in range(M)])
    E_m = np.array([K * (E_cmp + E_com)[a == m].sum() for m in range(M)])
    occ = np.array([(a == m).any() for m in range(M)])
    T_sum = I * (np.where(occ, T_cloud, 0) + T_m).max()
    E_sum = I * (np.where(occ, E_cloud, 0) + E_m).sum()
    R = E_sum + LAM * T_sum

    cb = system_model.evaluate(scn, assign, jnp.asarray(b, jnp.float32),
                               jnp.asarray(f), jnp.asarray(p), LAM)
    np.testing.assert_allclose(float(cb.T_sum), T_sum, rtol=1e-5)
    np.testing.assert_allclose(float(cb.E_sum), E_sum, rtol=1e-5)
    np.testing.assert_allclose(float(cb.R), R, rtol=1e-5)


# ------------------------------------------------------------------ invert
@settings(max_examples=50, deadline=None)
@given(G=st.floats(1e2, 1e10), frac=st.floats(0.01, 0.95))
def test_invert_rate_property(G, frac):
    """invert_rate returns the smallest b reaching any reachable target."""
    b_max = 1e7
    reachable = float(sroa.rate_fn(jnp.asarray(b_max), jnp.asarray(G)))
    target = frac * reachable
    b = float(sroa.invert_rate(jnp.asarray([G]), jnp.asarray([target]),
                               b_max)[0])
    got = float(sroa.rate_fn(jnp.asarray(b), jnp.asarray(G)))
    assert got >= target * (1 - 1e-3)
    if b > 1.0:  # minimality: slightly less bandwidth must miss the target
        less = float(sroa.rate_fn(jnp.asarray(b * 0.99), jnp.asarray(G)))
        assert less <= target * (1 + 1e-3)


def test_invert_rate_infeasible_returns_bmax():
    b = sroa.invert_rate(jnp.asarray([1e3]), jnp.asarray([1e9]), 1e6)
    assert float(b[0]) == pytest.approx(1e6)


def _rolled_invert_rate(G, target, b_max, iters=42):
    """Step-by-step oracle: the same bisection as a rolled device loop."""
    feas = sroa.rate_fn(jnp.full_like(G, b_max), G) >= target
    lo = jnp.zeros_like(G)
    hi = jnp.full_like(G, b_max)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        ok = sroa.rate_fn(mid, G) >= target
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    lo, hi = lax.fori_loop(0, iters, body, (lo, hi))
    return jnp.where(feas, hi, b_max)


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


def _inversion_case(kind, shape, seed=0, shared_b_max=False):
    """(G, target, b_max, b_max broadcast to ``shape``) for one edge of the
    inversion; b_max is a scalar, or one per cell for a (C, A, N) shape
    (the same in every cell with ``shared_b_max``)."""
    rng = np.random.default_rng(seed)
    G = (10.0 ** rng.uniform(2.0, 10.0, shape)).astype(np.float32)
    cells = shape[:1] if len(shape) > 1 else ()       # one b_max per cell
    b_max = (10.0 ** rng.uniform(5.0, 7.0, cells)).astype(np.float32)
    if shared_b_max:
        b_max = np.full_like(b_max, b_max.flat[0])
    bm = np.reshape(b_max, cells + (1,) * (len(shape) - len(cells)))
    sup = bm * np.log1p(G / bm) / np.log(2.0)
    target = {
        "reachable": rng.uniform(0.01, 0.95, shape) * sup,
        "unreachable": 1.5 * sup,                      # b_max returned
        "big": np.full(shape, sroa._BIG),
        "zero": np.where(rng.uniform(size=shape) < 0.5, 0.0, sup),
    }[kind].astype(np.float32)
    if kind == "zero":                                 # b = 0 and G = 0 edges
        G = np.where(rng.uniform(size=shape) < 0.5, 0.0, G).astype(np.float32)
    return (jnp.asarray(G), jnp.asarray(target), jnp.asarray(b_max),
            np.broadcast_to(bm, shape))


@pytest.mark.parametrize("iters", [30, 42])
@pytest.mark.parametrize("shape", [(8,), (2, 3, 8)])
@pytest.mark.parametrize("kind", ["reachable", "unreachable", "big", "zero"])
def test_invert_rate_bitwise_rolled_oracle(kind, shape, iters):
    """The unrolled inversion is bitwise the step-by-step rolled loop,
    alone and under the engine's double vmap (cells x candidates)."""
    G, target, b_max, b_max_full = _inversion_case(kind, shape)

    def call(fn):
        one = partial(fn, iters=iters)
        if len(shape) == 1:
            return jax.jit(one)(G, target, b_max)
        inner = jax.vmap(one, in_axes=(0, 0, None))
        return jax.jit(jax.vmap(inner))(G, target, b_max)

    got = call(sroa.invert_rate)
    want = call(_rolled_invert_rate)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    if kind == "unreachable":
        np.testing.assert_array_equal(_bits(got), _bits(b_max_full))


def test_solve_unrolled_inversion_bitwise_rolled(monkeypatch):
    """A double-vmapped full solve (cells x candidate assignments) gives
    bitwise the same b, f, p, t, R as the same solve on the rolled oracle."""
    spec = wireless.ScenarioSpec(N=8, M=3)
    cfg = sroa.SroaConfig(b_iters=30, f_iters=16, p_iters=14, t_iters=20)
    cells = [wireless.draw_scenario(s, spec) for s in (0, 1)]
    rng = np.random.default_rng(0)
    consts, args = [], []
    for scn in cells:
        assigns = jnp.stack(
            [wireless.nearest_edge_assignment(scn)]
            + [jnp.asarray(rng.integers(0, spec.M, spec.N), jnp.int32)
               for _ in range(2)])
        consts.append(system_model.sroa_constants_batched(scn, assigns))
        args.append((scn.B_open, scn.B_open, scn.f_max, scn.p_max, scn.N0))
    consts = jax.tree.map(lambda *x: jnp.stack(x), *consts)
    B, b_max, f_max, p_max, N0 = (jnp.stack(a) for a in zip(*args))

    def solve():
        one = partial(sroa.solve_constants_impl, cfg=cfg)
        inner = jax.vmap(one, in_axes=(0,) + (None,) * 6)
        out = jax.jit(jax.vmap(inner, in_axes=(0,) * 6 + (None,)))(
            consts, B, b_max, f_max, p_max, N0, jnp.float32(LAM))
        return out.b, out.f, out.p, out.t, out.R

    got = solve()
    monkeypatch.setattr(sroa, "invert_rate", _rolled_invert_rate)
    want = solve()
    assert bool(jnp.all(jnp.isfinite(got[4])))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _inversion_loops(text):
    """Shapes of the inversion's one-trip loops in a lowered program (each
    carries G, target, its flag and the upper bound)."""
    return re.findall(r"stablehlo\.while\(.*\) : tensor<([\dx]+)xf32>, "
                      r"tensor<\1xf32>, tensor<i1>, tensor<\1xf32>$", text,
                      re.M)


@pytest.mark.parametrize("b_max_kind", ["scalar", "per_cell"])
@pytest.mark.parametrize("shape", [(3, 21, 50), (2, 351, 50)])
@pytest.mark.parametrize("kind", ["reachable", "unreachable", "big", "zero"])
def test_lane_dense_inversion_bitwise_vmapped(kind, shape, b_max_kind):
    """The inversion run as one lane-dense stream of a (cells, candidates,
    N) batch, padded to whole tiles (3150 and 35100 elements are not whole
    tiles), is bitwise the inversion vmapped as laid out."""
    scalar = b_max_kind == "scalar"
    G, target, b_max, b_max_full = _inversion_case(kind, shape,
                                                   shared_b_max=scalar)
    if scalar:
        b_max = b_max[0]
    outer = None if scalar else 0

    @jax.jit
    def dense(G, target, b_max):
        per_problem = jnp.broadcast_to(
            b_max if scalar else b_max[:, None], shape[:-1])
        return sroa._dense_inverter(G, per_problem, 30)(target)

    got = dense(G, target, b_max)
    rows = -(-G.size // 1024) * 8
    assert _inversion_loops(dense.lower(G, target, b_max).as_text()) == [
        f"{rows}x128"]
    inner = jax.vmap(partial(sroa.invert_rate, iters=30),
                     in_axes=(0, 0, None))
    want = jax.jit(jax.vmap(inner, in_axes=(0, 0, outer)))(G, target, b_max)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    if kind == "unreachable":
        np.testing.assert_array_equal(_bits(got), _bits(b_max_full))


@pytest.mark.parametrize("cells,cands", [(3, 21), (2, 351)])
def test_solve_lane_dense_bitwise_tiled(cells, cands, monkeypatch):
    """Double-vmapped Algorithm 2 and whole solves (cells x candidates x
    N = 50) whose f search runs its inversions lane-dense give bitwise the
    b, f, p, t, R and b_sum of the same search vmapped as laid out (the
    parent's program)."""
    spec = wireless.ScenarioSpec()
    rng = np.random.default_rng(1)
    consts, args = [], []
    for seed in range(cells):
        scn = wireless.draw_scenario(seed, spec)
        assigns = jnp.asarray(rng.integers(0, spec.M, (cands, spec.N)),
                              jnp.int32)
        consts.append(system_model.sroa_constants_batched(scn, assigns))
        args.append((scn.B_open, scn.f_max, scn.p_max, scn.N0))
    consts = jax.tree.map(lambda *x: jnp.stack(x), *consts)
    B, f_max, p_max, N0 = (jnp.stack(a) for a in zip(*args))
    p = jnp.broadcast_to(p_max[:, None, :], consts.h.shape)
    t = jnp.full(consts.h.shape[:2], 50.0, jnp.float32)

    cfg = sroa.SroaConfig(b_iters=30, f_iters=6, p_iters=4, t_iters=4)

    def run(floor):
        # Neither route may reuse the other's traced program.
        jax.clear_caches()
        monkeypatch.setattr(sroa, "_DENSE_MIN_TILES", floor)

        def solve(c, B, f_max, p_max, N0):
            return sroa.solve_constants_impl(c, B, B, f_max, p_max, N0,
                                             jnp.float32(LAM), cfg)

        def alg2(c, p, t, B, f_max, N0):
            return sroa.algorithm2(c, p, t, B, B, f_max, N0, cfg)
        solves = jax.jit(jax.vmap(jax.vmap(solve,
                                           in_axes=(0,) + (None,) * 4)))
        alg2s = jax.jit(jax.vmap(jax.vmap(alg2,
                                          in_axes=(0, 0, 0) + (None,) * 3)))
        loops = _inversion_loops(
            alg2s.lower(consts, p, t, B, f_max, N0).as_text())
        out = solves(consts, B, f_max, p_max, N0)
        b, f, b_sum = alg2s(consts, p, t, B, f_max, N0)
        return loops, (out.b, out.f, out.p, out.t, out.R, out.b_sum,
                       b, f, b_sum)

    rows = -(-cells * cands * spec.N // 1024) * 8
    loops, got = run(1)
    assert loops == [f"{rows}x128"] * 2
    loops, want = run(10 ** 9)
    assert loops == [f"{cells}x{cands}x{spec.N}"] * 2
    assert bool(jnp.all(jnp.isfinite(got[4])))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("batch", [None, (16,)])
def test_inversion_shape_rule_keeps_small_batches_tiled(batch):
    """An unbatched (N,) search and the re-pricing's (16, 50) single-vmap
    batch keep the parent's program; the engine's buckets go lane-dense."""
    shape = (batch or ()) + (50,)
    G, target, b_max, _ = _inversion_case("reachable", shape)
    cfg = sroa.SroaConfig(b_iters=30, f_iters=5)
    u = jnp.ones(shape, jnp.float32)
    consts = system_model.SroaConstants(A=u, J=u, H=u * 1e4, delta=u * 0.1,
                                        h=G, E_cloud_total=u[..., 0])

    def alg2(c, B):
        return sroa.algorithm2(c, jnp.ones((50,)), 50.0, B, B,
                               jnp.full((50,), 1e9), 1.0, cfg)

    fn = jax.jit(alg2 if batch is None else jax.vmap(alg2))
    B = b_max if batch is None else jnp.broadcast_to(b_max, batch)
    loops = _inversion_loops(fn.lower(consts, B).as_text())
    assert loops == ["x".join(map(str, shape))] * 2
    if batch is not None:
        assert sroa.invert_tiles(shape) == (800, 2, False)
    for rows, cands in [(4, 201), (8, 201), (16, 201), (8, 351), (4, 301)]:
        elements, tiles, dense = sroa.invert_tiles((rows, cands, 50))
        assert dense and elements / (tiles * 1024) > 0.97


# -------------------------------------------------------------------- SROA
def test_sroa_feasible_and_respects_constraints(scn, assign, sroa_res):
    res = sroa_res
    assert bool(res.feasible)
    assert float(res.b_sum) <= float(scn.B_total) * (1 + 2e-3)   # (15a-b)
    assert bool(jnp.all(res.f <= scn.f_max * (1 + 1e-5)))        # (15c)
    assert bool(jnp.all(res.f >= 0))
    assert bool(jnp.all(res.p <= scn.p_max * (1 + 1e-5)))        # (15d)
    assert bool(jnp.all(res.p >= 0))


def test_sroa_deadline_met(scn, assign, sroa_res):
    """Every user's total delay (constraint 17d) is within t*."""
    cb = system_model.evaluate(scn, assign, sroa_res.b, sroa_res.f,
                               sroa_res.p, LAM)
    assert float(cb.T_sum) <= float(sroa_res.t) * (1 + 1e-2)


def test_sroa_internal_R_matches_system_model(scn, assign, sroa_res):
    """Algorithm 4's tracked R agrees with the eq-15 evaluation at t*."""
    cb = system_model.evaluate(scn, assign, sroa_res.b, sroa_res.f,
                               sroa_res.p, LAM)
    # internal R uses the deadline t >= achieved delay; E parts must agree
    internal_E = float(sroa_res.R) - LAM * float(sroa_res.t)
    np.testing.assert_allclose(internal_E, float(cb.E_sum), rtol=1e-2)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sroa_beats_every_baseline(seed):
    """Paper Fig 2: SROA achieves the lowest objective value."""
    scn = wireless.draw_scenario(seed)
    assign = wireless.nearest_edge_assignment(scn)
    scores = {}
    for name, fn in baselines.RA_METHODS.items():
        ra = fn(scn, assign, LAM)
        scores[name] = float(system_model.evaluate(
            scn, assign, ra.b, ra.f, ra.p, LAM).R)
    best = min(scores, key=scores.get)
    assert best == "SROA", scores


@pytest.mark.slow
def test_sroa_plus_no_worse_than_sroa(scn, assign, sroa_res):
    plus = sroa.solve_plus(scn, assign, LAM)
    assert float(plus.R) <= float(sroa_res.R) * (1 + 1e-6)


@pytest.mark.parametrize("lam", [1e-3, 1.0, 1e3])
def test_sroa_lambda_tradeoff(scn, assign, lam):
    """Fig 3 mechanics: larger lambda buys lower delay at higher energy."""
    res = sroa.solve(scn, assign, lam)
    assert bool(res.feasible)


def test_sroa_lambda_monotone_delay(scn, assign):
    """T_sum should (weakly) fall as lambda rises."""
    T = []
    for lam in [1e-2, 1.0, 1e2]:
        res = sroa.solve(scn, assign, lam)
        cb = system_model.evaluate(scn, assign, res.b, res.f, res.p, lam)
        T.append(float(cb.T_sum))
    assert T[2] <= T[0] * (1 + 5e-2)


def test_ofdma_quantization_feasible(scn, assign):
    ra = baselines.sroa_ra(scn, assign, LAM)
    q = baselines.to_ofdma(scn, ra)
    b = np.asarray(q.b, np.float64)
    assert b.sum() <= float(scn.B_total) * (1 + 1e-6)
    np.testing.assert_allclose(b % baselines.SUBCARRIER_HZ, 0, atol=1.0)
