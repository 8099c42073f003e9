"""Public jit'd wrappers for the Pallas kernels.

The mode follows the backend: on CPU the kernels run in the Pallas
interpreter (the kernel body executes exactly, without Mosaic lowering);
on a TPU they are lowered through Mosaic.  Any other backend is refused
rather than silently interpreted.  The raw kernels take ``interpret`` as a
required argument, so nothing reaches the chip in interpret mode by
default.  Model code selects these via ``ArchConfig.attn_impl='pallas'``
and ``SroaConfig.use_pallas=True`` / ``SroaConfig.fused=True``.
"""
from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import sroa_bisect as _sb
from repro.kernels import topk_moves as _tk


_KERNEL_CALL = re.compile(
    r'%([A-Za-z_][\w-]*?)(?:\.\d+)* = [^\n]*'
    r'custom_call_target="tpu_custom_call"')


def compiled_kernel_names(hlo_text: str) -> set[str]:
    """Mosaic kernels in a compiled TPU program (``compiled.as_text()``).

    Each kernel's ``pallas_call`` carries a stable ``name`` (``sroa_solve``,
    ``topk_moves``, ...), which names its ``tpu_custom_call`` instruction.
    A kernel that ran in interpret mode leaves no such instruction.
    """
    return set(_KERNEL_CALL.findall(hlo_text))


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"backend {backend!r} is neither")


@partial(jax.jit, static_argnames=("iters", "interpret"))
def sroa_invert_rate(G, target, b_max, iters: int = 42,
                     interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _sb.sroa_bisect_pallas(G, target, b_max, iters=iters,
                                  interpret=interpret)


@partial(jax.jit, static_argnames=("iters", "interpret"))
def sroa_invert_rate_batched(G, target, b_max, iters: int = 42,
                             interpret: bool | None = None):
    """Fleet-batched inversion: G, target (B, N); b_max (B,) or scalar.

    Flattens the batch so one kernel launch processes B*N users in full
    (8 x 128) tiles — this is the path `repro.fleet.batch.solve_batch`
    routes through when ``SroaConfig.use_pallas`` is set.
    """
    interpret = _default_interpret() if interpret is None else interpret
    shape = G.shape
    bm = jnp.broadcast_to(jnp.asarray(b_max, jnp.float32)[..., None], shape)
    out = _sb.sroa_bisect_pallas_vec(G.reshape(-1), target.reshape(-1),
                                     bm.reshape(-1), iters=iters,
                                     interpret=interpret)
    return out.reshape(shape)


@partial(jax.jit, static_argnames=("b_iters", "f_iters", "p_iters",
                                   "t_iters", "eps0", "eps1", "eps2",
                                   "t_low", "t_up", "interpret"))
def sroa_solve_batched(A, J, H, delta, h, f_max, p_max, B, b_max, N0, lam,
                       E_cloud_total, *, b_iters: int = 42,
                       f_iters: int = 40, p_iters: int = 36,
                       t_iters: int = 48, eps0: float = 1e-4,
                       eps1: float = 1e-4, eps2: float = 1e-4,
                       t_low: float = 1.0, t_up: float = 3e7,
                       interpret: bool | None = None):
    """Fused full-SROA solve: every (..., N)-leading axis in one launch.

    Per-user operands are (..., N); per-problem operands are (...) or
    scalar.  All leading axes flatten into the kernel's problem axis, so
    the engine's candidates-within-cells double vmap becomes a single
    Pallas call instead of four nested XLA while_loops per candidate.
    """
    interpret = _default_interpret() if interpret is None else interpret
    A = jnp.asarray(A, jnp.float32)
    lead, N = A.shape[:-1], A.shape[-1]
    P = 1
    for d in lead:
        P *= d

    def fu(x):
        return jnp.broadcast_to(jnp.asarray(x, jnp.float32),
                                lead + (N,)).reshape(P, N)

    def fs(x):
        return jnp.broadcast_to(jnp.asarray(x, jnp.float32),
                                lead).reshape(P)

    b, f, p, t, R, b_sum, feas = _sb.sroa_solve_pallas(
        fu(A), fu(J), fu(H), fu(delta), fu(h), fu(f_max), fu(p_max),
        fs(B), fs(b_max), fs(N0), fs(lam), fs(E_cloud_total),
        b_iters=b_iters, f_iters=f_iters, p_iters=p_iters, t_iters=t_iters,
        eps0=eps0, eps1=eps1, eps2=eps2, t_low=t_low, t_up=t_up,
        interpret=interpret)
    return (b.reshape(lead + (N,)), f.reshape(lead + (N,)),
            p.reshape(lead + (N,)), t.reshape(lead), R.reshape(lead),
            b_sum.reshape(lead), feas.reshape(lead))


@partial(jax.jit, static_argnames=("k", "interpret"))
def topk_move_scores(gain, H, p_max, assign, mask, N0, B, *, k: int,
                     interpret: bool | None = None):
    """Top-k move pruning: cheapest k (user, dst) moves per cell.

    gain is (..., N, M); H/p_max/assign/mask are (..., N); N0/B are (...)
    or scalar.  Leading axes flatten into the kernel's problem axis.
    Returns (user, dst, score), each (..., k); entries with
    ``score >= 1e29`` are padding (fewer than k valid moves).
    """
    interpret = _default_interpret() if interpret is None else interpret
    gain = jnp.asarray(gain, jnp.float32)
    lead, (N, M) = gain.shape[:-2], gain.shape[-2:]
    P = 1
    for d in lead:
        P *= d

    def fu(x, dtype):
        return jnp.broadcast_to(jnp.asarray(x, dtype),
                                lead + (N,)).reshape(P, N)

    def fs(x):
        return jnp.broadcast_to(jnp.asarray(x, jnp.float32),
                                lead).reshape(P)

    user, dst, score = _tk.topk_moves_pallas(
        gain.reshape(P, N, M), fu(H, jnp.float32), fu(p_max, jnp.float32),
        fu(assign, jnp.int32), fu(mask, jnp.float32), fs(N0), fs(B),
        k=k, interpret=interpret)
    return (user.reshape(lead + (k,)), dst.reshape(lead + (k,)),
            score.reshape(lead + (k,)))


@partial(jax.jit,
         static_argnames=("causal", "q_offset", "window", "interpret"))
def flash_attention(q, k, v, *, causal=True, q_offset=0, window=None,
                    interpret: bool | None = None):
    """q/k/v: (B, T, H, hd) [model layout] -> (B, T, H, hd).

    Pads head_dim to a multiple of 128 lanes, transposes to (B, H, T, hd)
    for the kernel, and undoes both on the way out.
    """
    interpret = _default_interpret() if interpret is None else interpret
    B, T, H, hd = q.shape
    pad = (-hd) % 128
    scale_fix = ((hd + pad) / hd) ** 0.5  # kernel scales by padded hd
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pad)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    qt = (q * scale_fix).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _fa.flash_attention_pallas(qt, kt, vt, causal=causal,
                                     q_offset=q_offset, window=window,
                                     interpret=interpret)
    out = out.transpose(0, 2, 1, 3)
    return out[..., :hd]


@partial(jax.jit, static_argnames=("eps", "interpret"))
def fused_rmsnorm(x, scale, eps: float = 1e-6,
                  interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _rn.rmsnorm_pallas(x, scale, eps=eps, interpret=interpret)
