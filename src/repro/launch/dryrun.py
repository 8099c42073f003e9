"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this AOT-compiles the real step function (train / prefill /
serve) against abstract inputs on the production mesh, then records:

* ``compiled.memory_analysis()``  — per-device bytes (proves fit / misfit),
* ``compiled.cost_analysis()``    — per-device HLO FLOPs / bytes,
* collective bytes parsed from the optimized HLO (all-gather, all-reduce,
  reduce-scatter, all-to-all, collective-permute output sizes),

and writes a JSON record consumed by EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
    python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs, optim
from repro.configs import shapes as shp
from repro.launch import mesh as mesh_lib
from repro.models import transformer as tf
from repro.runtime import sharding as sh

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([0-9,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
          "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
          "pred": 1}


def _shape_bytes(type_text: str) -> int:
    total = 0
    for dm in _SHAPE_RE.finditer(type_text):
        dt, dims = dm.group(1), dm.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES.get(dt.split("e")[0] if dt.startswith("f8")
                                else dt, 2)
    return total


def _split_computations(hlo_text: str) -> dict:
    """Split HLO text into computation blocks: name -> list of lines."""
    comps, name, buf = {}, None, []
    for line in hlo_text.splitlines():
        s = line.rstrip()
        if name is None:
            m = re.match(r"\s*(?:ENTRY\s+)?%([\w\.\-]+)\s*\(.*\{\s*$", s)
            if m:
                name, buf = m.group(1), []
        else:
            if s.strip() == "}":
                comps[name] = buf
                name = None
            else:
                buf.append(s.strip())
    return comps


def collective_bytes(hlo_text: str) -> dict:
    """Loop-aware collective traffic: output bytes of every collective op,
    multiplied by the trip count of any enclosing `while` (lax.scan layers).

    XLA's cost analysis counts while bodies ONCE; without this correction a
    scan-over-layers model under-reports per-layer collectives by ~n_layers.
    """
    comps = _split_computations(hlo_text)
    const_re = re.compile(r"s32\[\]\s*constant\((\d+)\)")
    while_re = re.compile(r"condition=%([\w\.\-]+),\s*body=%([\w\.\-]+)")
    call_re = re.compile(r"(?:calls=|to_apply=)%([\w\.\-]+)")
    branch_re = re.compile(r"branch_computations=\{([^}]*)\}")

    def trip_count(cond_name: str) -> int:
        consts = [int(x) for ln in comps.get(cond_name, [])
                  for x in const_re.findall(ln)]
        return max(consts) if consts else 1

    # Multiplier per computation: walk call graph from entry, scaling by
    # while trip counts (handles nested scans: layers x kv-chunks).
    mult: dict = {}

    def visit(comp: str, m: int):
        if comp not in comps or mult.get(comp, 0) >= m:
            return
        mult[comp] = m
        for ln in comps[comp]:
            wm = while_re.search(ln)
            if wm:
                visit(wm.group(2), m * trip_count(wm.group(1)))
            for cm in call_re.finditer(ln):
                visit(cm.group(1), m)
            bm = branch_re.search(ln)
            if bm:
                for name in re.findall(r"%([\w\.\-]+)", bm.group(1)):
                    visit(name, m)

    entry = None
    for ln in hlo_text.splitlines():
        m = re.match(r"\s*ENTRY\s+%([\w\.\-]+)", ln)
        if m:
            entry = m.group(1)
            break
    if entry:
        visit(entry, 1)

    out = {}
    for comp, lines in comps.items():
        m = mult.get(comp, 1)
        for ln in lines:
            cm = _COLL_RE.search(ln)
            if cm:
                op = cm.group(2)
                out[op] = out.get(op, 0) + _shape_bytes(cm.group(1)) * m
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def opt_state_axes(opt_name: str, axes_tree):
    is_axes = lambda x: isinstance(x, tuple)
    if opt_name == "sgd":
        return {"mu": axes_tree, "step": ()}
    if opt_name == "adamw":
        return {"m": axes_tree, "v": axes_tree, "step": ()}
    if opt_name == "adafactor":
        def f(axes):
            if len(axes) >= 2:
                return {"vr": tuple(axes[:-1]),
                        "vc": tuple(axes[:-2]) + (axes[-1],)}
            return {"v": axes}
        return {"mom": jax.tree.map(f, axes_tree, is_leaf=is_axes),
                "step": ()}
    raise ValueError(opt_name)


def shardings_for(mesh, rules, axes_tree, shapes_tree=None):
    """NamedShardings from logical axes; with shapes, drops mesh axes that
    do not divide the corresponding dim (pjit arguments must divide evenly —
    e.g. hubert's vocab=504, xlstm's 4 heads, B=1 long-decode caches)."""
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))

    def spec_for(axes, shape=None):
        parts = []
        for i, logical in enumerate(axes):
            ax = rules.mesh_axes(logical)
            if ax is not None and shape is not None:
                names = (ax,) if isinstance(ax, str) else tuple(ax)
                size = int(np.prod([axis_size[a] for a in names]))
                if shape[i] % size != 0:
                    ax = None
            parts.append(ax)
        return NamedSharding(mesh, P(*parts))

    if shapes_tree is None:
        return jax.tree.map(lambda axes: spec_for(axes), axes_tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree.map(
        lambda axes, sds: spec_for(axes, sds.shape), axes_tree, shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple))


def model_flops(cfg: tf.ArchConfig, shape: shp.ShapeSpec):
    """MODEL_FLOPS: 6*N_active*D for training, 2*N_active*D for inference."""
    defs = jax.tree.leaves(tf.param_defs(cfg), is_leaf=tf._is_def)
    total = sum(int(np.prod(d.shape)) for d in defs)
    active = total
    if cfg.n_experts:                      # subtract inactive expert params
        expert_like = 3 * cfg.n_experts * cfg.d_model * cfg.d_ff * \
            cfg.n_layers
        active_expert = 3 * cfg.top_k * cfg.d_model * cfg.d_ff * cfg.n_layers
        active = total - expert_like + active_expert
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * active * tokens, total, active


def analytic_terms(cfg: tf.ArchConfig, shape: shp.ShapeSpec,
                   n_devices: int) -> dict:
    """Roofline terms from first principles (XLA's cost_analysis counts
    while/scan bodies once, so the compiled numbers under-report depth;
    these analytics are the source of truth for §Roofline — the HLO-parsed
    collective bytes are loop-aware and used for the collective term).

    Executed FLOPs = model matmul FLOPs + attention/SSM mixing FLOPs
    (+ one extra forward when remat recomputes activations in training).
    """
    mf, total, active = model_flops(cfg, shape)
    B, T = shape.global_batch, shape.seq_len
    L, H, hd, Hkv = cfg.n_layers, cfg.n_heads, cfg.head_dim, cfg.n_kv_heads
    kind = shape.kind

    # --- mixing flops (attention / SSM), forward pass, global ---
    if kind == "decode":
        tq, ctx = 1, T
    else:
        tq, ctx = T, T
    mix_fwd = 0.0
    eff_ctx = min(cfg.window, ctx) if cfg.window else ctx
    causal_half = 0.5 if (cfg.causal and kind != "decode"
                          and not cfg.window) else 1.0
    attn_fwd_per_layer = 4.0 * B * tq * eff_ctx * H * hd * causal_half
    if cfg.family in ("dense", "moe", "encoder"):
        mix_fwd = L * attn_fwd_per_layer
    elif cfg.family == "mamba_hybrid":
        d_inner, Hm = tf.ssm_lib.mamba2_dims(cfg.d_model, cfg.ssm_state,
                                             cfg.ssm_headdim)
        ssm = 8.0 * B * tq * Hm * cfg.ssm_state * cfg.ssm_headdim * L
        n_attn = L // cfg.attn_every
        mix_fwd = ssm + n_attn * attn_fwd_per_layer
    elif cfg.family == "xlstm":
        hd2 = cfg.d_model // H
        mlstm = 8.0 * B * tq * H * hd2 * hd2 * (L // 2)
        slstm = 16.0 * B * tq * H * hd2 * hd2 * (L // 2)
        mix_fwd = mlstm + slstm

    fwd = mf / (6.0 if kind == "train" else 2.0) * 2.0 + mix_fwd
    if kind == "train":
        executed = 3.0 * fwd + (fwd if cfg.remat else 0.0)  # fwd+bwd(2x)+remat
        model = mf + 3.0 * mix_fwd
    else:
        executed = fwd
        model = mf + mix_fwd

    # --- HBM traffic per device ---
    p_local = total / n_devices            # all params sharded (FSDP/TP/EP)
    dtype_b = 2.0
    if kind == "train":
        opt_bytes = {"adamw": 16.0, "sgd": 8.0, "adafactor": 1.0}[
            cfg.optimizer]
        # fwd read + bwd read + grad w/r + opt state r/w + param write
        param_traffic = p_local * (3 * dtype_b + 4.0 + opt_bytes + dtype_b)
        # wide intermediates (ff/heads) are model-sharded, batch dp-sharded:
        # treat activation traffic as fully sharded across the mesh.
        act_traffic = B * T * cfg.d_model * L * 20.0 / n_devices
    elif kind == "prefill":
        param_traffic = p_local * dtype_b
        act_traffic = B * T * cfg.d_model * L * 8.0 / n_devices
    else:  # decode: read params + KV/state
        active_local = active / n_devices
        param_traffic = active_local * dtype_b
        if cfg.family in ("dense", "moe"):
            kv = L * B * T * Hkv * hd * 2 * dtype_b
        elif cfg.family == "mamba_hybrid":
            d_inner, Hm = tf.ssm_lib.mamba2_dims(cfg.d_model, cfg.ssm_state,
                                                 cfg.ssm_headdim)
            W = min(cfg.window or T, T)
            kv = L * B * Hm * cfg.ssm_state * cfg.ssm_headdim * 4 * 2 + \
                (L // cfg.attn_every) * B * W * Hkv * hd * 2 * dtype_b
        else:
            hd2 = cfg.d_model // H
            kv = (L // 2) * B * H * hd2 * (hd2 + 4) * 4 * 2 * 2
        act_traffic = kv / n_devices
    hbm_bytes = param_traffic + act_traffic

    return {
        "flops_model_global": model,
        "flops_executed_global": executed,
        "flops_executed_per_device": executed / n_devices,
        "hbm_bytes_per_device": hbm_bytes,
        "compute_term_s": executed / n_devices / mesh_lib.PEAK_FLOPS_BF16,
        "memory_term_s": hbm_bytes / mesh_lib.HBM_BW,
    }


def _dp_size(n_devices: int) -> int:
    return 32 if n_devices == 512 else 16


import dataclasses


def apply_variant(cfg, rules, variant: str, n_devices: int, multi_pod: bool):
    """Named perf variants (§Perf hillclimb iterations)."""
    dp = ("pod", "data") if multi_pod else ("data",)
    for piece in variant.split("+"):
        if piece in ("baseline", ""):
            continue
        elif piece == "moe_local":
            # device-local MoE dispatch: no cross-device cumsum/scatter
            cfg = dataclasses.replace(cfg, moe_dispatch_groups=n_devices)
            rules = dataclasses.replace(
                rules, moe_groups=dp + ("model",),
                moe_groups_ep=dp, expert_cap=None)
        elif piece == "sp":
            # Megatron-style sequence-parallel residual stream
            rules = dataclasses.replace(rules, resid_seq=("model",))
        elif piece == "kv_seq":
            # decode KV cache sharded over context (sequence-parallel decode)
            rules = dataclasses.replace(rules, kv_seq=("model",))
        elif piece == "no_fsdp":
            # inference: weights TP-only (no per-layer FSDP gathers)
            rules = dataclasses.replace(rules, d_model=None)
        elif piece == "no_remat":
            cfg = dataclasses.replace(cfg, remat=False)
        else:
            raise ValueError(f"unknown variant piece {piece!r}")
    return cfg, rules


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules: sh.ShardingRules | None = None, tag: str = "baseline",
             donate: bool = True, variant: str = "baseline") -> dict:
    cfg = configs.get(arch)
    shape = shp.SHAPES[shape_name]
    ok, reason = shp.applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "tag": tag}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rules = rules or sh.default_rules(multi_pod=multi_pod)
    cfg, rules = apply_variant(cfg, rules, variant, mesh.devices.size,
                               multi_pod)
    rec["variant"] = variant
    shard = sh.make_sharder(mesh, rules)

    p_axes = tf.logical_axes(cfg)
    p_abs = tf.abstract_params(cfg)
    p_shard = shardings_for(mesh, rules, p_axes, p_abs)
    batch_abs = shp.batch_specs(cfg, shape)
    b_axes = shp.batch_logical_axes(cfg, shape)
    b_shard = shardings_for(mesh, rules, b_axes, batch_abs)
    repl = NamedSharding(mesh, P())

    t0 = time.time()
    if shape.kind == "train":
        opt = optim.get_optimizer(cfg.optimizer)
        o_abs = jax.eval_shape(opt.init, p_abs)
        o_axes = opt_state_axes(cfg.optimizer, p_axes)
        o_shard = shardings_for(mesh, rules, o_axes, o_abs)
        step = tf.make_train_step(cfg, opt, shard=shard)
        metr_shard = {"ce": repl, "aux": repl, "loss": repl,
                      "grad_norm": repl}
        jitted = jax.jit(step,
                         in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, metr_shard),
                         donate_argnums=(0, 1) if donate else ())
        lowered = jitted.lower(p_abs, o_abs, batch_abs)
    elif shape.kind == "prefill":
        step = tf.make_prefill_step(cfg, shard=shard)
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
        lowered = jitted.lower(p_abs, batch_abs)
    else:  # decode
        step = tf.make_serve_step(cfg, shard=shard)
        c_shard = b_shard["cache"]
        t_shard = b_shard["tokens"]
        jitted = jax.jit(step,
                         in_shardings=(p_shard, c_shard, t_shard),
                         out_shardings=(None, c_shard),
                         donate_argnums=(1,) if donate else ())
        lowered = jitted.lower(p_abs, batch_abs["cache"],
                               batch_abs["tokens"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    mf, n_total, n_active = model_flops(cfg, shape)
    terms = analytic_terms(cfg, shape, mesh.devices.size)
    terms["collective_term_s"] = coll["total"] / mesh_lib.ICI_BW

    def g(obj, attr):
        try:
            v = getattr(obj, attr, None)
            return int(v) if v is not None else None
        except Exception:
            return None

    rec.update(
        status="ok",
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        n_devices=mesh.devices.size,
        params_total=n_total, params_active=n_active,
        model_flops_global=mf,
        flops_per_device=float(cost.get("flops", -1.0)) if cost else None,
        bytes_per_device=float(cost.get("bytes accessed", -1.0))
        if cost else None,
        memory={k: g(mem, k) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes")} if mem else None,
        collectives=coll,
        roofline=terms,
    )
    return rec


def main():
    # 512 placeholder host devices for the production meshes.  Set before
    # the first device query (jax fixes the device count when its backend
    # starts) and never at import: tests and the planning service import
    # this module and must keep their own device view.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--variant", default="baseline",
                    help="'+'-joined: moe_local, sp, kv_seq, no_remat")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()

    archs = list(configs.ARCHS) if args.arch == "all" else [args.arch]
    shape_names = list(shp.SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    for arch in archs:
        for shape_name in shape_names:
            for mp in meshes:
                mesh_tag = "multipod" if mp else "singlepod"
                fname = outdir / f"{arch}__{shape_name}__{mesh_tag}__" \
                    f"{args.tag}.json"
                if fname.exists():
                    print(f"[skip-cached] {fname.name}")
                    continue
                print(f"[dryrun] {arch} x {shape_name} x {mesh_tag} ...",
                      flush=True)
                try:
                    rec = run_cell(arch, shape_name, mp, tag=args.tag,
                                   variant=args.variant)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if mp else "16x16",
                           "tag": args.tag, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                fname.write_text(json.dumps(rec, indent=1))
                print(f"  -> {rec['status']}"
                      + (f" compile={rec.get('compile_s')}s"
                         if rec["status"] == "ok" else
                         f" ({rec.get('reason') or rec.get('error')})"),
                      flush=True)


if __name__ == "__main__":
    main()
