"""Batched SROA over stacked scenarios (the fleet engine's data plane).

A :class:`FleetScenario` stacks C heterogeneous cells — each its own
:class:`~repro.core.wireless.Scenario` with its own user count, bandwidth
budget, and model size — into one pytree with a common padded user axis and
a validity mask.  :func:`solve_batch` then runs the paper's full Algorithm 4
for every cell in ONE jitted XLA call: `jax.vmap` over
:func:`repro.core.sroa.solve_constants` keeps each cell's bisection
trajectory bit-identical to a standalone solve (the batched `while_loop`
freezes finished cells element-wise), while the inner bandwidth inversion
can be routed through the Pallas kernel (``SroaConfig.use_pallas``), whose
custom batching rule flattens the whole (C, N) batch into full (8 x 128)
tiles — see :func:`repro.kernels.ops.sroa_invert_rate_batched`.

Padded users are neutralized through
:func:`repro.core.system_model.mask_constants`: their rate targets, compute
loads, and energies are all zero, so they cost ~b_max * 2**-iters of
bandwidth each (measure zero against any budget).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sroa
from repro.core.system_model import (SroaConstants, sroa_constants,
                                     sroa_constants_batched)
from repro.core.wireless import (Scenario, ScenarioSpec, draw_scenario,
                                 nearest_edge_assignment)

# Scenario fields carrying a leading user axis (everything else is per-edge
# or scalar and stacks as-is).
_PER_USER_FIELDS = ("user_pos", "gain", "c", "D", "f_max", "p_max",
                    "tier", "cycle_mult", "size_mult")


class FleetScenario(NamedTuple):
    """C cells stacked on a leading axis, padded to a common user count."""

    cells: Scenario         # every leaf stacked: (C, ...) per cell
    mask: jnp.ndarray       # (C, N_max) bool — True = real user
    n_users: jnp.ndarray    # (C,) int32 true user count per cell

    @property
    def C(self) -> int:
        return self.mask.shape[0]

    @property
    def N_max(self) -> int:
        return self.mask.shape[1]

    @property
    def M(self) -> int:
        return self.cells.edge_pos.shape[-2]

    @property
    def edge_mask(self) -> jnp.ndarray | None:
        """(C, M) bool activation mask, or None when all sites are live (D12)."""
        return self.cells.edge_mask

    def cell(self, i: int) -> Scenario:
        """The i-th cell as a standalone, unpadded Scenario."""
        s = jax.tree.map(lambda x: x[i], self.cells)
        n = int(self.n_users[i])
        cut = {name: getattr(s, name)[:n] for name in _PER_USER_FIELDS}
        return s._replace(**cut)


def _pad_users(scn: Scenario, n_max: int) -> Scenario:
    """Pad every per-user leaf to n_max by replicating the last user.

    Replication keeps the padded rows physically plausible (finite gains,
    in-range compute constants); correctness never depends on them because
    the fleet mask zeroes their SROA constants.
    """
    pad = n_max - scn.N
    if pad == 0:
        return scn
    out = {}
    for name in _PER_USER_FIELDS:
        x = getattr(scn, name)
        reps = jnp.repeat(x[-1:], pad, axis=0)
        out[name] = jnp.concatenate([x, reps], axis=0)
    return scn._replace(**out)


def stack_scenarios(scns: Sequence[Scenario],
                    n_max: int | None = None) -> Scenario:
    """Stack scenarios (same M; user counts may differ) on a leading axis."""
    n_max = n_max or max(s.N for s in scns)
    ms = {s.M for s in scns}
    if len(ms) != 1:
        raise ValueError(f"all cells must share an edge count, got {ms}")
    padded = [_pad_users(s, n_max) for s in scns]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *padded)


def fleet_from_scenarios(scns: Sequence[Scenario]) -> FleetScenario:
    """Wrap standalone scenarios into a padded, masked FleetScenario."""
    ns = np.array([s.N for s in scns], np.int32)
    n_max = int(ns.max())
    mask = jnp.asarray(np.arange(n_max)[None, :] < ns[:, None])
    return FleetScenario(cells=stack_scenarios(scns, n_max), mask=mask,
                         n_users=jnp.asarray(ns))


def draw_fleet(seed: int, n_cells: int, spec: ScenarioSpec | None = None, *,
               n_range: tuple[int, int] = (24, 56),
               b_scale_range: tuple[float, float] = (0.5, 2.0),
               s_scale_range: tuple[float, float] = (0.5, 2.0)
               ) -> FleetScenario:
    """Draw a heterogeneous fleet of cells.

    Each cell varies independently in user count (``n_range``), per-edge
    bandwidth budget (paper range scaled by ``b_scale_range``), and model
    size (``s_scale_range`` x the spec's s_bytes) — the "many cells, many
    model sizes" regime the fleet engine amortizes over.
    """
    spec = spec or ScenarioSpec()
    rng = np.random.default_rng(seed)
    cells = []
    for _ in range(n_cells):
        n = int(rng.integers(n_range[0], n_range[1] + 1))
        k_b = float(rng.uniform(*b_scale_range))
        k_s = float(rng.uniform(*s_scale_range))
        lo, hi = spec.B_edge_range_hz
        cell_spec = dataclasses.replace(
            spec, N=n, B_edge_range_hz=(lo * k_b, hi * k_b),
            s_bytes=spec.s_bytes * k_s)
        cells.append(draw_scenario(int(rng.integers(2 ** 31)), cell_spec))
    return fleet_from_scenarios(cells)


def fleet_assignments(fleet: FleetScenario) -> jnp.ndarray:
    """(C, N_max) nearest-edge init for every cell (Alg 5 line 5)."""
    return jax.vmap(nearest_edge_assignment)(fleet.cells)


def fleet_constants(fleet: FleetScenario, assigns: jnp.ndarray,
                    comps: jnp.ndarray | None = None,
                    ladder=None) -> SroaConstants:
    """Masked, per-cell SROA constants with a leading (C,) axis.

    ``comps`` (C, N_max) with a ``ladder`` prices each user's chosen
    compression level into the constants (D11); None keeps the literal
    uncompressed pricing.
    """
    if comps is None:
        return jax.vmap(sroa_constants)(fleet.cells, assigns, fleet.mask)
    fn = lambda s, a, m, cp: sroa_constants(s, a, m, cp,     # noqa: E731
                                            ladder)
    return jax.vmap(fn)(fleet.cells, assigns, fleet.mask,
                        jnp.asarray(comps, jnp.int32))


@partial(jax.jit, static_argnames=("cfg",))
@jax.named_scope("reprice")
def solve_constants_batch(consts: SroaConstants, B, b_max, f_max, p_max, N0,
                          lam, cfg: sroa.SroaConfig = sroa.SroaConfig()
                          ) -> sroa.SroaResult:
    """vmap of Algorithm 4 over pre-stacked constants — one XLA call.

    Every argument carries a leading batch axis: per-user leaves are
    (B, N), per-scenario scalars are (B,).  Results stack the same way.
    Its device ops carry the ``reprice`` scope: on the served path this is
    the re-pricing of :func:`solve_batch` (the scope must sit inside the
    jitted function, since a scope around an eager call of it is lost).
    """
    def one(c, B_, bm, fm, pm, n0, l):
        return sroa.solve_constants(c, B_, bm, fm, pm, n0, l, cfg)

    return jax.vmap(one)(consts, B, b_max, f_max, p_max, N0, lam)


def solve_batch(fleet: FleetScenario, assigns: jnp.ndarray | None = None,
                lam=1.0, cfg: sroa.SroaConfig = sroa.SroaConfig(),
                comps: jnp.ndarray | None = None, ladder=None
                ) -> sroa.SroaResult:
    """Batched SROA for a whole fleet: C scenarios solved in one jitted call.

    Args:
      fleet:   stacked cells.
      assigns: (C, N_max) int32 per-cell assignments (nearest-edge default).
      lam:     scalar or (C,) objective weight(s).
      comps:   optional (C, N_max) int32 per-user compression levels,
               priced through ``ladder`` (D11).
    Returns:
      SroaResult with leading (C,) axes; entries of padded users carry
      ~zero bandwidth and are ignored by downstream aggregates.
    """
    if assigns is None:
        assigns = fleet_assignments(fleet)
    consts = fleet_constants(fleet, assigns, comps, ladder)
    em = fleet.cells.edge_mask
    B = (jnp.sum(fleet.cells.B_edges, axis=-1) if em is None else
         jnp.sum(jnp.where(em, fleet.cells.B_edges, 0.0), axis=-1))
    lam_v = jnp.broadcast_to(jnp.asarray(lam, jnp.float32), (fleet.C,))
    return solve_constants_batch(consts, B, B, fleet.cells.f_max,
                                 fleet.cells.p_max, fleet.cells.N0, lam_v,
                                 cfg)


def candidate_assigns_device(assign: jnp.ndarray, M: int,
                             movable: jnp.ndarray | None = None,
                             edge_mask: jnp.ndarray | None = None
                             ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Device-resident single-move neighbourhood with fixed-size padding.

    Row 0 is the current pattern; rows 1..N*(M-1) move user ``n`` to edge
    ``(assign[n] + k) % M`` for k in 1..M-1 (every edge except its own).
    The candidate count ``A = 1 + N*(M-1)`` depends only on the static
    shapes — never on the mask — so churn (users toggling in ``movable``)
    and topology changes (sites toggling in ``edge_mask``, D12) re-flag
    rows in the returned validity vector instead of changing any array
    shape, and the engine's jitted search never recompiles.

    Returns:
      cands: (A, N) int32 candidate patterns.
      valid: (A,) bool — False rows (moves of non-movable users, or moves
             landing on a closed edge site) must be excluded from any
             argmin by the caller.
    """
    assign = jnp.asarray(assign, jnp.int32)
    N = assign.shape[0]
    if movable is None:
        movable = jnp.ones((N,), bool)
    offs = jnp.arange(1, M, dtype=jnp.int32)
    dst = (assign[:, None] + offs[None, :]) % M            # (N, M-1)
    eye = jnp.eye(N, dtype=bool)
    moves = jnp.where(eye[:, None, :], dst[:, :, None],
                      assign[None, None, :])               # (N, M-1, N)
    cands = jnp.concatenate([assign[None], moves.reshape(N * (M - 1), N)])
    move_ok = jnp.repeat(jnp.asarray(movable, bool), M - 1)
    if edge_mask is not None:
        move_ok = move_ok & edge_mask[dst.reshape(-1)]
    valid = jnp.concatenate([jnp.ones((1,), bool), move_ok])
    return cands, valid


def solve_candidates(scn: Scenario, assigns: jnp.ndarray, lam=1.0,
                     cfg: sroa.SroaConfig = sroa.SroaConfig(),
                     mask: jnp.ndarray | None = None) -> sroa.SroaResult:
    """Batched SROA for A candidate assignments of ONE scenario.

    The batched-TSIA inner loop: every candidate single-user move is
    scored in the same XLA call instead of one host round trip each.
    """
    assigns = jnp.asarray(assigns, jnp.int32)
    A = assigns.shape[0]
    consts = sroa_constants_batched(scn, assigns, mask)
    tile = lambda x: jnp.broadcast_to(x, (A,) + jnp.shape(x))  # noqa: E731
    lam_v = jnp.broadcast_to(jnp.asarray(lam, jnp.float32), (A,))
    B = tile(scn.B_open)
    return solve_constants_batch(consts, B, B, tile(scn.f_max),
                                 tile(scn.p_max), tile(scn.N0), lam_v, cfg)
