"""§Perf cell C: the paper's hierarchy on the multi-pod mesh.

Lowers deepseek-67b train_4k on the 2x16x16 mesh two ways:
  (1) standard synchronous DP over ('pod','data')  — baseline train_step;
  (2) HFL-LM (Algorithm 1): K pod-local steps + one cross-pod average.
and compares collective bytes *per microbatch step* — the paper's claim is
that hierarchy divides the upper-tier (cloud / cross-pod) traffic by K.

Run inside the dry-run environment:
  PYTHONPATH=src python -m benchmarks.cell_c [--K 4] [--arch deepseek-67b]
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs, optim
from repro.configs import shapes as shp
from repro.fed import hfl_lm
from repro.launch import dryrun as d
from repro.launch import mesh as mesh_lib
from repro.models import transformer as tf
from repro.runtime import sharding as sh

OUT = Path(__file__).resolve().parents[1] / "results" / "cell_c.json"

import re


def crosspod_collective_bytes(hlo_text: str, pod_size: int = 256) -> dict:
    """Like dryrun.collective_bytes but split into {intra, cross}-pod by
    reconstructing each op's replica groups (iota or explicit form)."""
    comps = d._split_computations(hlo_text)
    const_re = re.compile(r"s32\[\]\s*constant\((\d+)\)")
    while_re = re.compile(r"condition=%([\w\.\-]+),\s*body=%([\w\.\-]+)")
    call_re = re.compile(r"(?:calls=|to_apply=)%([\w\.\-]+)")
    mult = {}

    def trip(c):
        cs = [int(x) for ln in comps.get(c, []) for x in const_re.findall(ln)]
        return max(cs) if cs else 1

    def visit(c, m):
        if c not in comps or mult.get(c, 0) >= m:
            return
        mult[c] = m
        for ln in comps[c]:
            wm = while_re.search(ln)
            if wm:
                visit(wm.group(2), m * trip(wm.group(1)))
            for cm in call_re.finditer(ln):
                visit(cm.group(1), m)

    entry = [n for n in comps if n.startswith("main")]
    if entry:
        visit(entry[0], 1)

    iota_re = re.compile(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
    expl_re = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")

    def spans_pods(ln) -> bool:
        m = iota_re.search(ln)
        if m:
            G, S = int(m.group(1)), int(m.group(2))
            dims = [int(x) for x in m.group(3).split(",")]
            ids = np.arange(int(np.prod(dims)))
            if m.group(4):
                perm = [int(x) for x in m.group(4).split(",")]
                ids = ids.reshape(dims).transpose(perm).reshape(-1)
            groups = ids.reshape(G, S)
            pods = groups // pod_size
            return bool((pods.min(1) != pods.max(1)).any())
        m = expl_re.search(ln)
        if m:
            for grp in re.findall(r"\{([0-9,]*)\}", m.group(1)):
                ids = np.array([int(x) for x in grp.split(",") if x])
                if ids.size and (ids // pod_size).min() != \
                        (ids // pod_size).max():
                    return True
            return False
        return True      # unknown format: assume cross-pod (conservative)

    out = {"intra": 0, "cross": 0}
    for c, lines in comps.items():
        m = mult.get(c, 1)
        for ln in lines:
            cm = d._COLL_RE.search(ln)
            if cm:
                key = "cross" if spans_pods(ln) else "intra"
                out[key] += d._shape_bytes(cm.group(1)) * m
    return out


def lower_standard(cfg, shape, mesh, rules):
    shard = sh.make_sharder(mesh, rules)
    p_axes = tf.logical_axes(cfg)
    p_abs = tf.abstract_params(cfg)
    p_shard = d.shardings_for(mesh, rules, p_axes, p_abs)
    batch_abs = shp.batch_specs(cfg, shape)
    b_shard = d.shardings_for(mesh, rules,
                              shp.batch_logical_axes(cfg, shape), batch_abs)
    opt = optim.get_optimizer(cfg.optimizer)
    o_abs = jax.eval_shape(opt.init, p_abs)
    o_shard = d.shardings_for(
        mesh, rules, d.opt_state_axes(cfg.optimizer, p_axes), o_abs)
    repl = NamedSharding(mesh, P())
    step = tf.make_train_step(cfg, opt, shard=shard)
    jt = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                 out_shardings=(p_shard, o_shard,
                                {"ce": repl, "aux": repl, "loss": repl,
                                 "grad_norm": repl}),
                 donate_argnums=(0, 1))
    return jt.lower(p_abs, o_abs, batch_abs).compile()


def lower_hfl(cfg, shape, mesh, rules, K, pods=2):
    # intra-pod rules: batch over 'data' only; pod handled by stacking
    rules = sh.ShardingRules(**{**rules.__dict__, "batch": ("data",)})
    shard = sh.make_sharder(mesh, rules)
    p_abs = hfl_lm.stacked_abstract(cfg, pods)
    p_axes = hfl_lm.stacked_axes(cfg)
    p_shard = d.shardings_for(mesh, rules, p_axes, p_abs)
    opt = optim.get_optimizer(cfg.optimizer)
    o_abs = jax.eval_shape(jax.vmap(opt.init), p_abs)   # per-pod opt state
    o_axes = d.opt_state_axes(cfg.optimizer, p_axes)
    o_axes["step"] = ("hfl_pod",)
    o_shard = d.shardings_for(mesh, rules, o_axes, o_abs)
    # batches: (P, K, B/P, T) — same global tokens per outer step as K
    # standard steps
    B, T = shape.global_batch, shape.seq_len
    batch_abs = {"tokens": jax.ShapeDtypeStruct(
        (pods, K, B // pods, T), jax.numpy.int32)}
    b_shard = {"tokens": NamedSharding(
        mesh, P("pod", None, "data", None))}
    repl = NamedSharding(mesh, P())
    step = hfl_lm.make_hfl_lm_train_step(cfg, opt, K=K, shard=shard)
    jt = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                 out_shardings=(p_shard, o_shard, {"ce": repl}),
                 donate_argnums=(0, 1))
    return jt.lower(p_abs, o_abs, batch_abs).compile()


def main():
    # Placeholder devices for the multi-pod mesh, set before the backend
    # starts and never at import (see repro.launch.dryrun.main).
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-67b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--K", type=int, default=4)
    ap.add_argument("--variant", default="sp")
    args = ap.parse_args()

    cfg = configs.get(args.arch)
    shape = shp.SHAPES[args.shape]
    mesh = mesh_lib.make_production_mesh(multi_pod=True)
    rules = sh.default_rules(multi_pod=True)
    cfg, rules = d.apply_variant(cfg, rules, args.variant,
                                 mesh.devices.size, True)

    print("[cell C] lowering standard sync-DP step ...", flush=True)
    c1 = lower_standard(cfg, shape, mesh, rules)
    hlo1 = c1.as_text()
    coll1 = d.collective_bytes(hlo1)
    split1 = crosspod_collective_bytes(hlo1)
    print("[cell C] lowering HFL-LM step (K =", args.K, ") ...", flush=True)
    c2 = lower_hfl(cfg, shape, mesh, rules, args.K)
    hlo2 = c2.as_text()
    coll2 = d.collective_bytes(hlo2)
    split2 = crosspod_collective_bytes(hlo2)

    K = args.K
    rec = {
        "arch": args.arch, "shape": args.shape, "K": K,
        "variant": args.variant,
        "std_total_per_microbatch": coll1["total"],
        "hfl_total_per_microbatch": coll2["total"] / K,
        "std_cross_pod_per_microbatch": split1["cross"],
        "hfl_cross_pod_per_microbatch": split2["cross"] / K,
        "std_intra_pod_per_microbatch": split1["intra"],
        "hfl_intra_pod_per_microbatch": split2["intra"] / K,
        "cross_pod_reduction":
            split1["cross"] / max(split2["cross"] / K, 1),
        "std_collectives": coll1, "hfl_collectives": coll2,
    }
    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(rec, indent=1))
    print(json.dumps({k: v for k, v in rec.items()
                      if "collectives" not in k}, indent=1))


if __name__ == "__main__":
    main()
