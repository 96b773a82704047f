import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture x input shape x
mesh) cell against the production meshes with 512 placeholder CPU devices.

This is the proof artifact that the distribution config is coherent: a
sharding mismatch, an OOM at compile, or an unsupported collective fails
here. Outputs per cell:
  * memory_analysis()  — per-device bytes (argument/output/temp): fits 16 GB?
  * cost_analysis()    — raw XLA numbers (recorded; see roofline.py caveat)
  * loop-aware HLO parse — dot FLOPs + collective bytes (analysis/roofline)
  * the three roofline terms + dominant bound

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                    # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod        # 2x16x16
"""

import argparse
import json
import time
import traceback

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.analysis import roofline as RL
from repro.configs import (ARCHS, SHAPES, cell_is_runnable, get_config,
                           input_specs)
from repro.dist import sharding as Sh
from repro.launch import steps as St
from repro.launch.mesh import make_production_mesh

BATCH_AXES = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "pos": ("batch",),
    "positions": ("batch", "seq", None),
    "audio_embed": ("batch", None, "embed_act"),
    "vision_embed": ("batch", None, "embed_act"),
}

_CACHE_LEAF_AXES = {
    "k": ("batch", "kv_seq", "kv_heads_act", None),
    "v": ("batch", "kv_seq", "kv_heads_act", None),
    "k_sc": ("batch", "kv_seq", "kv_heads_act"),
    "v_sc": ("batch", "kv_seq", "kv_heads_act"),
    "xk": ("batch", None, "kv_heads_act", None),
    "xv": ("batch", None, "kv_heads_act", None),
    "s": ("batch", "rnn_act", None, None),
    "h": ("batch", "rnn_act"),
    "conv": ("batch", None, "rnn_act"),
    "shift_t": ("batch", None, "embed_act"),
    "shift_c": ("batch", None, "embed_act"),
}


def _cache_axes(path, leaf):
    name = Sh._leaf_name(path)
    axes = _CACHE_LEAF_AXES.get(name, (None,) * len(leaf.shape))
    nd = len(leaf.shape)
    if nd > len(axes):
        axes = (None,) * (nd - len(axes)) + tuple(axes)
    return tuple(axes)[:nd] if nd < len(axes) else axes


def _opt_axes(path, leaf):
    """Optimizer state: moments are shape-aligned with params (sharding.py
    resolves the q/sc/f moment suffixes to the parent param's axes)."""
    return Sh.logical_axes_for(path, leaf)


def pick_rules(shape, cfg=None, n_devices: int = 256) -> str:
    if shape.name == "long_500k":
        return "long"
    if shape.kind == "train":
        # small models: pure DP+FSDP — TP-16 on <3B params is pure collective
        # overhead (EXPERIMENTS.md §Perf, small-model appendix). Only when
        # the global batch shards over EVERY mesh axis; otherwise the idle
        # axis replicates activations (measured 94 GB/dev on whisper pod2).
        if (cfg is not None and cfg.n_params() < 3e9
                and shape.global_batch % n_devices == 0):
            return "train_dp"
        return "train"
    return {"prefill": "prefill", "decode": "serve"}[shape.kind]


def pick_optimizer(cfg) -> optim.Optimizer:
    """int8-moment Adam for the very large models (DESIGN.md §6)."""
    if cfg.n_params() > 5e10:
        return optim.int8_adam(optim.warmup_cosine(3e-4, 100, 10000))
    return optim.adamw(optim.warmup_cosine(3e-4, 100, 10000))


def _with_opt_flat(rules: dict) -> dict:
    return {**rules, "opt_flat": ("data", "model")}


def build_cell(arch: str, shape_name: str, mesh):
    """Returns (fn, arg_sds, in_shardings, out_shardings, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    preset = pick_rules(shape, cfg, n_dev)
    if preset == "train_dp":
        # 1 batch row/device: grad-accumulation microbatching would reshape
        # across the fully-sharded batch dim (involuntary resharding)
        import dataclasses as _dc
        cfg = _dc.replace(cfg, microbatch=1)
    rules = _with_opt_flat(Sh.PRESETS[preset])
    specs = input_specs(cfg, shape)
    batch_shardings = Sh.tree_specs(
        specs, mesh, rules,
        lambda p, l: BATCH_AXES.get(Sh._leaf_name(p), (None,) * len(l.shape)))

    if shape.kind == "train":
        opt = pick_optimizer(cfg)
        state_sds = St.abstract_train_state(cfg, opt, mode="qat")
        state_sh = {
            "params": Sh.param_specs(state_sds["params"], mesh, rules),
            "opt_state": Sh.tree_specs(state_sds["opt_state"], mesh, rules,
                                       _opt_axes),
            "step": NamedSharding(mesh, P()),
        }
        step_fn = St.make_train_step(cfg, opt, mode="qat")

        def fn(state, batch):
            with Sh.use_rules(mesh, rules):
                return step_fn(state, batch)

        out_sh = (state_sh, None)
        return (fn, (state_sds, specs), (state_sh, batch_shardings), out_sh,
                dict(cfg=cfg, shape=shape, quantized=False))

    # serving cells: packed-weight params
    params_sds = St.abstract_serve_params(cfg)
    params_sh = Sh.param_specs(params_sds, mesh, rules)
    if shape.kind == "prefill":
        step_fn = St.make_prefill_step(cfg)

        def fn(params, batch):
            with Sh.use_rules(mesh, rules):
                return step_fn(params, batch)

        # returned decode caches shard like the serve preset (kv_seq -> model)
        cache_sds = jax.eval_shape(fn, params_sds, specs)[1]
        serve_rules = _with_opt_flat(Sh.PRESETS["serve"])
        cache_sh = Sh.tree_specs(cache_sds, mesh, serve_rules, _cache_axes)
        return (fn, (params_sds, specs), (params_sh, batch_shardings),
                (None, cache_sh),
                dict(cfg=cfg, shape=shape, quantized=True))

    # decode
    cache_sds = St.abstract_cache(cfg, shape.global_batch, shape.seq_len)
    cache_sh = Sh.tree_specs(cache_sds, mesh, rules, _cache_axes)
    step_fn = St.make_decode_step(cfg)

    def fn(params, caches, batch):
        with Sh.use_rules(mesh, rules):
            return step_fn(params, caches, batch)

    in_sh = (params_sh, cache_sh, batch_shardings)
    out_sh = (None, cache_sh)
    return (fn, (params_sds, cache_sds, specs), in_sh, out_sh,
            dict(cfg=cfg, shape=shape, quantized=True))


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             keep_hlo: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}
    t0 = time.time()
    fn, sds, in_sh, out_sh, meta = build_cell(arch, shape_name, mesh)
    donate = (0,) if shape.kind == "train" else ((1,) if shape.kind == "decode" else ())
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    lowered = jitted.lower(*sds)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    stats = RL.parse_hlo(hlo, bf16_model=(meta["cfg"].dtype == "bfloat16"))
    rl = RL.roofline(stats, meta["cfg"], meta["shape"], n_dev,
                     quantized=meta["quantized"])

    per_dev_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok",
        "n_devices": n_dev,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_bytes": per_dev_bytes,
            "fits_16GB": bool(per_dev_bytes < 16e9),
        },
        "xla_cost_analysis": {k: float(v) for k, v in cost.items()
                              if "flops" in k or k == "bytes accessed"},
        "hlo_parse": {
            "dot_flops_per_dev": stats.dot_flops,
            "collective_bytes": stats.collective_bytes,
            "n_collectives": stats.n_collectives,
            "n_while": stats.n_while,
            "unknown_trip_counts": stats.unknown_trip_counts,
        },
        "roofline": rl,
    }
    if keep_hlo:
        result["hlo_text"] = hlo
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    rows = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"{'pod2' if mp else 'pod1'}_{arch}_{shape}"
                try:
                    res = run_cell(arch, shape, multi_pod=mp)
                except Exception as e:
                    res = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "FAILED", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=1)
                rows.append(res)
                s = res["status"]
                extra = ""
                if s == "ok":
                    gb = res["memory"]["per_device_bytes"] / 1e9
                    rl = res["roofline"]
                    extra = (f"mem/dev={gb:.2f}GB bound={rl['bound']} "
                             f"c/m/x={rl['compute_s']:.3e}/{rl['memory_s']:.3e}/"
                             f"{rl['collective_s']:.3e}s "
                             f"compile={res['compile_s']}s")
                elif s == "skipped":
                    extra = res["reason"][:60]
                else:
                    extra = res["error"][:120]
                print(f"[{s:7s}] {tag:55s} {extra}", flush=True)

    n_ok = sum(r["status"] == "ok" for r in rows)
    n_skip = sum(r["status"] == "skipped" for r in rows)
    n_fail = sum(r["status"] == "FAILED" for r in rows)
    print(f"\n== dry-run: {n_ok} ok, {n_skip} skipped (documented), {n_fail} FAILED ==")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
