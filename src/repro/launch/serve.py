"""Batched serving driver with packed 2-bit weights (the paper's deployment
form): offline weight quantize+pack -> prefill -> token-by-token decode.

CPU-runnable on reduced configs; the decode step is the same function the
``decode_*`` dry-run cells lower against the production mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
      --batch 4 --prompt-len 32 --gen 16

Quantized execution plans (docs/quantization.md): by default the model is
packed under a kernel-backed plan — ``--w-bits``/``--a-bits``/
``--group-size`` build it, or ``--plan NAME`` picks a preset from
repro.core.qplan.PLANS (e.g. ``w2a2``, ``w2a16g128``, ``mixed_attn4_mlp2``).
Every plan-covered dense then dispatches through kernels/ops (lut_gemm for
w{b}a{b}, dequant_matmul for w{b}a16) in prefill AND decode — including
through the paged engine. ``--plan legacy`` restores the historical
dequant-einsum serving forward.

``--paged`` drives the continuous-batching Engine (serving/engine.py)
instead of the fixed-batch loop: a mixed-length request stream is admitted
through chunked prefill into the paged block-pool cache, with per-token
streaming, admission control (``--max-queue``) and preemption on block
exhaustion. ``--prefix-cache`` turns on the prefix-sharing radix cache
(requests with a common block-aligned prompt prefix attach already-filled
blocks instead of re-prefilling them) and ``--prefill-batch N`` fuses up to
N requests per prefill chunk step:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
      --paged --requests 12 --block-size 16 --gen 16 \
      --prefix-cache --prefill-batch 4
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_for_smoke
from repro.core.qlinear import QuantPolicy
from repro.core.qplan import PLANS, get_plan, make_plan
from repro.models import lm, frontends
from repro.launch import compile_cache, steps as St
from repro.launch.mesh import make_tp_mesh
from repro.obs import Tracer, metrics as obs_metrics
from repro.serving import Engine, Request, SamplerConfig


def validate_args(args, cfg) -> None:
    """Reject incoherent flag combinations LOUDLY instead of silently
    auto-disabling features the caller asked for. Raises ValueError with an
    actionable message (main() surfaces it through argparse.error)."""
    recurrent = any(t in ("recurrent", "rwkv") for t in cfg.pattern)
    if args.prefix_cache and not args.paged:
        raise ValueError(
            "--prefix-cache requires --paged: the radix cache shares blocks "
            "of the paged engine's pool; the fixed-batch loop has no blocks "
            "to share")
    if args.prefill_batch > 1 and not args.paged:
        raise ValueError(
            "--prefill-batch requires --paged: batched prefill chunks are a "
            "paged-engine feature (the fixed-batch loop already prefills "
            "every request in one batch)")
    if args.tp > 1 and not args.paged:
        raise ValueError(
            "--tp requires --paged: tensor-parallel serving runs through "
            "the engine's mesh-parameterized step functions")
    if args.prefix_cache and recurrent:
        raise ValueError(
            f"--prefix-cache is incompatible with recurrent arch "
            f"'{cfg.name}': per-slot recurrent state has no block boundary "
            "to share at (attention-only archs support prefix sharing)")
    if args.prefix_cache and args.prefill == "whole":
        raise ValueError(
            "--prefix-cache is incompatible with --prefill whole: "
            "whole-prompt admission recomputes from scratch and cannot "
            "consume cached blocks; use --prefill chunked")
    if args.spec_draft_plan is not None:
        if not args.paged:
            raise ValueError(
                "--spec-draft-plan requires --paged: speculative decoding "
                "runs through the engine's draft/verify step functions")
        if args.prefill == "whole":
            raise ValueError(
                "--spec-draft-plan is incompatible with --prefill whole: "
                "the drafter's catch-up prefill replays the fed-token "
                "stream in chunks; use --prefill chunked")
        if recurrent:
            raise ValueError(
                f"--spec-draft-plan is incompatible with recurrent arch "
                f"'{cfg.name}': the drafter cannot rewind per-slot scan "
                "state past rejected tokens (attention-only archs only)")
        if args.spec_draft_plan not in PLANS:
            raise ValueError(
                f"--spec-draft-plan '{args.spec_draft_plan}' is not a "
                f"known plan preset ({', '.join(sorted(PLANS))})")
    if args.spec_k < 1:
        raise ValueError(f"--spec-k must be >= 1, got {args.spec_k}")
    if args.temperature < 0:
        raise ValueError(
            f"--temperature must be >= 0 (0 = greedy), got "
            f"{args.temperature}")
    if not 0.0 < args.top_p <= 1.0:
        raise ValueError(
            f"--top-p must be in (0, 1] (1 = off), got {args.top_p}")
    if args.top_k < 0:
        raise ValueError(f"--top-k must be >= 0 (0 = off), got {args.top_k}")
    if args.a_scale == "static" and args.plan is None and args.a_bits is None:
        raise ValueError(
            "--a-scale static requires an activation-quantized plan: pass "
            "--a-bits N (or a --plan with a_bits set) so there is an "
            "activation scale to calibrate")
    if args.a_scale == "static" and args.plan == "legacy":
        raise ValueError(
            "--a-scale static is incompatible with --plan legacy: the "
            "legacy dequant-einsum forward has no activation quantization "
            "to calibrate a scale for")
    if args.kv_splits != "auto":
        try:
            ks = int(args.kv_splits)
        except ValueError:
            raise ValueError(
                f"--kv-splits must be 'auto' or a positive integer, got "
                f"{args.kv_splits!r}") from None
        if ks < 1:
            raise ValueError(f"--kv-splits must be >= 1, got {ks}")
        if not args.paged:
            raise ValueError(
                "--kv-splits requires --paged: split-KV flash decode "
                "partitions the paged engine's block tables; the "
                "fixed-batch loop has no block tables to split")
        if recurrent:
            raise ValueError(
                f"--kv-splits is incompatible with recurrent arch "
                f"'{cfg.name}': per-slot scan state has no KV axis to "
                "partition (attention-only archs support split-KV decode)")
    if args.ring:
        if not args.paged:
            raise ValueError(
                "--ring requires --paged: ring-paged local layers replace "
                "the paged engine's full-length block tables; the "
                "fixed-batch loop already folds local windows densely")
        if not any(t == "local" for t in cfg.pattern) or not cfg.window:
            raise ValueError(
                f"--ring requires a sliding-window arch: '{cfg.name}' has "
                "no local attention layers to ring-page")
        if args.prefix_cache:
            raise ValueError(
                "--ring is incompatible with --prefix-cache: ring blocks "
                "are per-slot and rewritten in place, so local-layer KV "
                "can never be shared across requests")
    if args.trace_out and not args.paged:
        raise ValueError(
            "--trace-out requires --paged: request-lifecycle tracing hooks "
            "into the paged engine's scheduling loop (the fixed-batch loop "
            "has no per-request lifecycle to trace)")
    if args.metrics_out and not args.paged:
        raise ValueError(
            "--metrics-out requires --paged: the metrics snapshot is the "
            "paged engine's per-engine registry (docs/observability.md)")
    if args.tp < 1:
        raise ValueError(f"--tp must be >= 1, got {args.tp}")
    if args.tp > 1:
        import jax
        n = len(jax.devices())
        if args.tp > n:
            raise ValueError(
                f"--tp {args.tp} needs {args.tp} devices but only {n} are "
                "visible (on CPU, set XLA_FLAGS=--xla_force_host_platform_"
                "device_count=N before starting)")


def make_engine(cfg, qparams, args, mesh=None, spec=None) -> Engine:
    """The paged engine as ``--paged`` serves with it (chip_smoke.py builds
    its engines here too). ``spec`` is an optional (draft_cfg,
    draft_params) pair enabling self-speculative decoding
    (--spec-draft-plan)."""
    max_len = args.prompt_len + args.gen + args.block_size
    max_len = -(-max_len // args.block_size) * args.block_size
    sampler = SamplerConfig(temperature=args.temperature, top_k=args.top_k,
                            top_p=args.top_p, seed=args.seed)
    spec_kw = {}
    if spec is not None:
        dcfg, dparams = spec
        spec_kw = dict(spec_draft_params=dparams, spec_draft_cfg=dcfg,
                       spec_k=args.spec_k)
    return Engine(cfg, qparams, n_slots=args.batch, max_len=max_len,
                  block_size=args.block_size, max_queue=args.max_queue,
                  prefill=args.prefill,
                  prefix_cache=args.prefix_cache,
                  prefill_batch=args.prefill_batch, mesh=mesh,
                  sampler=sampler,
                  tracer=Tracer() if args.trace_out else None,
                  ring=args.ring, kv_splits=args.kv_splits, **spec_kw)


def serve_paged(cfg, qparams, args, mesh=None, spec=None) -> int:
    """Continuous-batching serve loop over the paged engine (make_engine)."""
    key = jax.random.PRNGKey(args.seed)
    engine = make_engine(cfg, qparams, args, mesh=mesh, spec=spec)
    tracer = engine.tracer
    if mesh is not None:
        print(f"  tensor-parallel over {mesh.shape['model']} devices: "
              f"{engine.per_device_weight_bytes()/1e3:.1f} KB weights "
              f"per device")
    t0 = time.time()
    first_tok: dict[int, float] = {}

    def stream(uid):
        def cb(tok, done):
            first_tok.setdefault(uid, time.time())
            if done:
                print(f"  [req {uid}] done at +{time.time()-t0:.2f}s")
        return cb

    lens = jax.random.randint(key, (args.requests,), 4,
                              args.prompt_len + 1)
    reqs = []
    for i in range(args.requests):
        P = int(lens[i])
        prompt = jax.random.randint(jax.random.fold_in(key, i), (P,),
                                    0, cfg.vocab_size)
        r = Request(uid=i, prompt=prompt, max_new=args.gen,
                    on_token=stream(i))
        reqs.append(r)
        if not engine.submit(r):
            print(f"  [req {i}] rejected (queue full)")
    m = engine.run()
    dt = time.time() - t0
    done = [r for r in reqs if r.done]
    n_tok = sum(len(r.out) for r in done)
    ttfts = [first_tok[r.uid] - t0 for r in done if r.uid in first_tok]
    print(f"  paged engine: {len(done)}/{len(reqs)} requests, {n_tok} tokens "
          f"in {dt:.2f}s ({len(done)/max(dt, 1e-9):.2f} req/s, "
          f"{n_tok/max(dt, 1e-9):.1f} tok/s)")
    print(f"  mean TTFT {1e3*sum(ttfts)/max(len(ttfts),1):.0f} ms | "
          f"decode steps {m['decode_steps']}, prefill chunks "
          f"{m['prefill_chunks']}, preemptions {m['preemptions']}, "
          f"util {m['slot_utilization']:.2f}, jit entries {m['n_compiles']}")
    if m.get("spec") is not None:
        sp = m["spec"]
        print(f"  spec decode: {sp['accepted_tokens_per_step']:.2f} tokens/"
              f"slot-step (acceptance {sp['acceptance_rate']:.2f} over "
              f"{sp['draft_tokens']} drafts, {sp['draft_evictions']} "
              f"drafter evictions)")
    if m["prefix_cache"] is not None:
        total = m["prefill_tokens_computed"] + m["prefill_tokens_shared"]
        print(f"  prefix cache: {m['prefill_tokens_shared']}/{total} prompt "
              f"tokens attached from cache "
              f"({m['prefix_cache']['cached_blocks']} blocks cached, "
              f"{m['prefix_cache']['evictions']} evictions)")
    counts = {k: v for k, v in m["metrics"]["counters"].items()
              if k.startswith("kernel_dispatch_total")}
    if counts:
        ops = {}
        for k, v in counts.items():
            op = dict(p.split("=", 1) for p in
                      k[k.index("{") + 1:-1].split(","))["op"]
            ops[op] = ops.get(op, 0) + int(v)
        print(f"  kernel dispatches (trace-time): {ops}")
    if tracer is not None:
        lat = tracer.latency_summary()
        ph = tracer.phase_summary()

        def p(stat):
            s = lat[stat]
            if not s["count"]:
                return f"{stat}: n/a"
            return (f"{stat} p50/p95/p99 {1e3*s['p50']:.0f}/"
                    f"{1e3*s['p95']:.0f}/{1e3*s['p99']:.0f} ms")
        print(f"  latency: {p('ttft_s')} | {p('tpot_s')}")
        tot = ph["total_s"]
        print("  phases (s): " + ", ".join(
            f"{k}={tot[k]:.3f}" for k in sorted(tot)))
        tracer.export(args.trace_out)
        kind = ("JSONL" if args.trace_out.endswith(".jsonl")
                else "chrome trace; load in ui.perfetto.dev")
        print(f"  trace written to {args.trace_out} ({kind})")
    if args.metrics_out:
        with open(args.metrics_out, "w") as fh:
            json.dump(m, fh, indent=1, default=float)
        print(f"  metrics snapshot written to {args.metrics_out}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--w-bits", type=int, default=2)
    ap.add_argument("--a-bits", type=int, default=None,
                    help="dynamic activation bits: w{b}a{b} LUT-GEMM plan "
                         "(default: weight-only w{b}a16)")
    ap.add_argument("--group-size", type=int, default=None,
                    help="group-wise weight-scale group along K "
                         "(default: per-output-channel)")
    ap.add_argument("--plan", default=None,
                    help=f"named plan preset ({', '.join(sorted(PLANS))}) "
                         "or 'legacy' for the historical dequant-einsum "
                         "path; overrides --w-bits/--a-bits/--group-size")
    ap.add_argument("--nonuniform", action="store_true",
                    help="k-means codebook (paper §5.3 non-uniform support)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged continuous-batching engine")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV-cache block size (tokens)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="engine admission queue bound")
    ap.add_argument("--requests", type=int, default=12,
                    help="number of mixed-length requests (--paged)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share block-aligned prompt prefixes through the "
                         "radix cache (--paged)")
    ap.add_argument("--prefill-batch", type=int, default=1,
                    help="requests fused per prefill chunk step (--paged)")
    ap.add_argument("--prefill", default="chunked",
                    choices=("chunked", "whole"),
                    help="paged-engine admission mode (whole replays the "
                         "legacy dense batcher's whole-prompt prefill)")
    ap.add_argument("--kv-splits", default="auto",
                    help="split-KV flash-decode chunks per decode step "
                         "(--paged): 'auto' picks from the max KV blocks "
                         "per slot (1 at short max-len, i.e. the "
                         "single-pass trace), or an explicit count >= 1")
    ap.add_argument("--ring", action="store_true",
                    help="ring-paged local layers (--paged, sliding-window "
                         "archs): local-attention KV lives in a fixed "
                         "per-slot ring of ~ceil(window/block_size) blocks "
                         "from a dedicated pool, so local-layer memory per "
                         "request stays flat in context length "
                         "(token-identical to full tables, not bitwise)")
    ap.add_argument("--spec-draft-plan", default=None,
                    help="enable self-speculative decoding (--paged): pack "
                         "a SECOND copy of the weights under this plan "
                         "preset (e.g. w2a2) as the drafter; the serving "
                         "plan's model verifies spec-k drafts per round "
                         "with lossless rejection sampling")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per speculative round")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest-probability tokens "
                         "(0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: minimal covering probability "
                         "mass (1 = off)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: serve over a (tp,)-device "
                         "'model' mesh (--paged; weights, LUT kernels and "
                         "the paged KV pool shard over the mesh)")
    ap.add_argument("--trace-out", default=None,
                    help="write the request-lifecycle + step-phase trace "
                         "here after the run (--paged): .jsonl for line-"
                         "delimited records, anything else for Chrome "
                         "trace JSON (Perfetto-loadable)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine metrics()/registry snapshot as "
                         "JSON here after the run (--paged)")
    ap.add_argument("--a-scale", default="dynamic",
                    choices=("dynamic", "static"),
                    help="w{b}a{b} activation scales: dynamic per-token "
                         "(default) or static, calibrated offline over "
                         "--calib-batches sample batches")
    ap.add_argument("--calib-batches", type=int, default=4,
                    help="sample batches for --a-scale static calibration")
    return ap


def plan_for(args):
    """(quant config, description) the flags select: --plan legacy, a named
    preset, or make_plan over --w-bits/--a-bits/--group-size."""
    if args.plan == "legacy":
        quant = QuantPolicy(w_bits=args.w_bits, nonuniform=args.nonuniform)
        desc = f"legacy w{args.w_bits} (dequant-einsum)"
    elif args.plan is not None:
        quant = get_plan(args.plan)
        if args.a_scale == "static":
            # retarget the preset's activation-quantized policies at static
            # scales — otherwise the calibration below would run and then
            # be silently discarded by quantize_tree (plan policies default
            # to a_scale='dynamic')
            quant = dataclasses.replace(quant, rules=tuple(
                (pat, dataclasses.replace(pol, a_scale="static")
                 if pol is not None and pol.a_bits is not None else pol)
                for pat, pol in quant.rules))
        desc = f"plan '{args.plan}'"
    else:
        quant = make_plan(args.w_bits, args.a_bits, args.group_size,
                          nonuniform=args.nonuniform, a_scale=args.a_scale)
        a = f"a{args.a_bits}" if args.a_bits else "a16"
        g = f" g{args.group_size}" if args.group_size else ""
        s = " static-a" if args.a_scale == "static" else ""
        desc = f"plan w{args.w_bits}{a}{g}{s}"
    return quant, desc


def main():
    ap = build_parser()
    args = ap.parse_args()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    try:
        validate_args(args, cfg)
    except ValueError as e:
        ap.error(str(e))
    compile_cache.enable()
    quant, desc = plan_for(args)
    cfg = dataclasses.replace(cfg, quant=quant)

    key = jax.random.PRNGKey(args.seed)
    B, P = args.batch, args.prompt_len
    print(f"[serve] {cfg.name}: packing weights under {desc} "
          f"({'k-means' if args.nonuniform else 'uniform'} codebook)")
    params = lm.init_params(key, cfg, mode="plain")

    act_scales = None
    if args.a_scale == "static":
        t0 = time.time()
        batches = [{"tokens": jax.random.randint(
            jax.random.fold_in(key, 1000 + i), (B, P), 0, cfg.vocab_size)}
            for i in range(args.calib_batches)]
        act_scales = lm.calibrate_act_scales(params, cfg, batches)
        print(f"  calibrated {len(act_scales)} layer classes over "
              f"{args.calib_batches} batches in {time.time()-t0:.2f}s")

    t0 = time.time()
    obs_metrics.global_registry().clear(obs_metrics.KERNEL_DISPATCH)
    # packed eagerly, not under jit: a plan with ``tune`` times its tile
    # candidates here, which needs real kernel runs, not a trace
    qparams = jax.block_until_ready(lm.quantize_tree(
        params, cfg, tp=args.tp, act_scales=act_scales))
    bf16_bytes = sum(x.size * 2 for x in jax.tree.leaves(params))
    q_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(qparams))
    print(f"  packed in {time.time()-t0:.2f}s: {bf16_bytes/1e6:.1f} MB bf16 "
          f"-> {q_bytes/1e6:.1f} MB packed ({bf16_bytes/q_bytes:.2f}x)")

    if args.paged:
        mesh = make_tp_mesh(args.tp) if args.tp > 1 else None
        spec = None
        if args.spec_draft_plan:
            dcfg = dataclasses.replace(cfg,
                                       quant=get_plan(args.spec_draft_plan))
            t0 = time.time()
            dparams = jax.block_until_ready(
                lm.quantize_tree(params, dcfg, tp=args.tp))
            d_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(dparams))
            print(f"  drafter packed under plan '{args.spec_draft_plan}' "
                  f"in {time.time()-t0:.2f}s: {d_bytes/1e6:.1f} MB "
                  f"(spec-k {args.spec_k})")
            spec = (dcfg, dparams)
        return serve_paged(cfg, qparams, args, mesh=mesh, spec=spec)

    kw = {}
    if cfg.is_encdec:
        kw["audio_embed"] = frontends.stub_audio_embed(
            key, B, cfg.encoder_seq, cfg.d_model)
    if cfg.n_vision_tokens:
        kw["vision_embed"] = frontends.stub_vision_embed(
            key, B, cfg.n_vision_tokens, cfg.d_model)

    tokens = jax.random.randint(key, (B, P), 0, cfg.vocab_size)
    max_len = P + args.gen

    prefill = jax.jit(St.make_prefill_step(cfg, max_len=max_len))
    decode = jax.jit(St.make_decode_step(cfg), donate_argnums=(1,))

    t0 = time.time()
    pf_batch = {"tokens": tokens, **kw}
    if cfg.mrope_sections:
        pf_batch["positions"] = frontends.mrope_positions(
            B, P, cfg.n_vision_tokens)
    logits, caches = prefill(qparams, pf_batch)
    caches = jax.block_until_ready(caches)
    t_prefill = time.time() - t0
    print(f"  prefill {B}x{P}: {t_prefill*1e3:.1f} ms")

    out_tokens = [jnp.argmax(logits[:, -1], -1)]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.full((B,), P + i, jnp.int32)
        batch = {"tokens": out_tokens[-1][:, None], "pos": pos}
        if cfg.mrope_sections:
            batch["positions"] = jnp.broadcast_to(
                (P + i) + jnp.zeros((B, 1, 3), jnp.int32), (B, 1, 3))
        logits, caches = decode(qparams, caches, batch)
        out_tokens.append(jnp.argmax(logits[:, -1], -1))
    jax.block_until_ready(out_tokens[-1])
    t_dec = time.time() - t0
    n_tok = B * (args.gen - 1)
    print(f"  decode: {n_tok} tokens in {t_dec*1e3:.1f} ms "
          f"({n_tok/max(t_dec,1e-9):.1f} tok/s)")
    gen = jnp.stack(out_tokens, axis=1)
    print(f"  sample generation (batch 0): {gen[0].tolist()}")
    counts = {k: v for k, v
              in obs_metrics.global_registry().dispatch_counts().items()
              if ":" not in k}
    if counts:
        print(f"  kernel dispatches (trace-time): {counts}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
