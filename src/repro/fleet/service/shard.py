"""Multi-device sharding of the fleet engine over the cell axis.

D5 padding makes every per-cell shape static, so a fleet shards trivially:
``shard_map`` splits the leading (C,) axis across a 1-D device mesh and
each device runs the vmapped device-resident search
(:func:`repro.fleet.engine.engine_core`) on its local cells — no
cross-device communication at all (cells are independent problems).

On a single device (CPU CI, laptops) :func:`solve_fleet_sharded` degrades
to the plain jitted :func:`repro.fleet.engine.solve_fleet_assignments`
call — same results, same API.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import sroa
from repro.fleet import batch as fbatch
from repro.fleet import engine as fengine
from repro.runtime.sharding import cell_mesh  # noqa: F401  (re-export)


@lru_cache(maxsize=None)
def _sharded_solver(mesh: Mesh, cfg: sroa.SroaConfig, max_rounds: int,
                    escape_iters: int, top_k: int = 0, n_starts: int = 1,
                    switch_cost: float = 0.0, ladder=None):
    """Build (once per mesh/config) the jitted shard-mapped fleet solver.

    The optional operands — horizon gain stacks + incumbents (D10),
    per-user init comps (D11), receding-horizon warm-start tails — ride in
    ONE extras pytree whose ``None`` members are empty subtrees: each
    on/off combination is a distinct treedef, so the jit wrapper compiles
    one program per combination without hand-written local variants, and
    every present leaf shards over the cell axis like the fleet leaves.
    ``ladder`` (a hashable :class:`repro.fed.compression.CompressionLadder`)
    joins the cache key because it reaches the engine as a static.
    """
    axis = mesh.axis_names[0]

    def local(cells, init, mask, lam_v, extras):
        def one(cell, ia, mk, lam, ex):
            gs, inc, cp, tl = ex
            return fengine.search_core(cell, ia, mk, lam, cfg,
                                       max_rounds, escape_iters, top_k,
                                       n_starts, gs, switch_cost, inc,
                                       ladder, cp, tl)
        return jax.vmap(one)(cells, init, mask, lam_v, extras)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(axis),) * 5,
                       out_specs=P(axis),
                       # the engine is a lax.while_loop, which has no
                       # replication rule — and needs none: every input
                       # and output is fully sharded over the cell axis.
                       check_vma=False)
    return jax.jit(fn)


def _pad_rows(tree, pad: int):
    """Pad every leaf's leading axis by repeating the last row."""
    return jax.tree.map(
        lambda x: jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)]),
        tree)


def solve_fleet_sharded(fleet: fbatch.FleetScenario,
                        init_assigns: jnp.ndarray | None = None,
                        lam=1.0,
                        cfg: sroa.SroaConfig = sroa.SroaConfig(),
                        max_rounds: int = 48, escape_iters: int = 6,
                        mesh: Mesh | None = None, top_k: int = 0,
                        n_starts: int = 1,
                        gain_stacks: jnp.ndarray | None = None,
                        switch_cost: float = 0.0,
                        incumbents: jnp.ndarray | None = None,
                        ladder=None,
                        init_comps: jnp.ndarray | None = None,
                        tail_inits: jnp.ndarray | None = None
                        ) -> fengine.EngineResult:
    """Fleet-wide assignment search, sharded over devices when available.

    ``mesh`` is a 1-D cell mesh (``repro.runtime.sharding.cell_mesh``);
    None runs the single-device path.  C is padded up to a multiple of the
    device count by repeating the last cell (its duplicate rows are
    dropped from the result), so any fleet size works on any mesh.
    ``top_k``/``n_starts`` are the engine's sub-quadratic search knobs
    (DESIGN.md D9); ``gain_stacks`` (C, K, N, M) with
    ``switch_cost``/``incumbents`` the rolling-horizon knobs (D10) — the
    per-cell predicted stacks shard over the cell axis like every other
    fleet leaf; ``tail_inits`` (C, N) the receding-horizon warm starts.
    """
    if init_assigns is None:
        init_assigns = fbatch.fleet_assignments(fleet)
    if gain_stacks is not None and gain_stacks.shape[1] == 1 \
            and switch_cost == 0.0:
        # K=1 + zero switching charge == snapshot planning; route through
        # the snapshot program for bitwise parity (see engine.py).
        gain = jnp.asarray(gain_stacks[:, 0], fleet.cells.gain.dtype)
        fleet = fleet._replace(cells=fleet.cells._replace(gain=gain))
        gain_stacks = incumbents = None
    if mesh is None:
        return fengine.solve_fleet_assignments(
            fleet, init_assigns, lam, cfg, max_rounds, escape_iters,
            top_k, n_starts, gain_stacks=gain_stacks,
            switch_cost=switch_cost, incumbents=incumbents,
            ladder=ladder, init_comps=init_comps, tail_inits=tail_inits)
    C = fleet.C
    ndev = int(np.prod(mesh.devices.shape))
    pad = (-C) % ndev
    init = jnp.asarray(init_assigns, jnp.int32)
    lam_v = jnp.broadcast_to(jnp.asarray(lam, jnp.float32), (C,))
    cells, mask = fleet.cells, fleet.mask
    horizon = gain_stacks is not None
    comp_on = fengine._comp_enabled(ladder)
    gs = jnp.asarray(gain_stacks, jnp.float32) if horizon else None
    incs = (init if incumbents is None
            else jnp.asarray(incumbents, jnp.int32)) if horizon else None
    comps = (jnp.zeros(init.shape, jnp.int32) if init_comps is None
             else jnp.asarray(init_comps, jnp.int32)) if comp_on else None
    tails = (None if tail_inits is None
             else jnp.asarray(tail_inits, jnp.int32))
    operands = [cells, init, mask, lam_v, (gs, incs, comps, tails)]
    if pad:
        operands = [_pad_rows(t, pad) for t in operands]
    out = _sharded_solver(mesh, cfg, max_rounds, escape_iters, top_k,
                          n_starts, float(switch_cost), ladder)(*operands)
    if pad:
        out = jax.tree.map(lambda x: x[:C], out)
    return out
