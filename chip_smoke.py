"""Chip smoke test: serve qwen1.5-0.5b at its published widths on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # tensor-parallel engine, 4 chips vs 1

One chip: the paged engine, built as ``repro.launch.serve --paged`` builds
it, serves the same 8 requests (prompts of 64-256 tokens, 32 greedy new
tokens each, random weights from --seed) under the bf16, w2a16 (serve.py's
default packed plan) and w4a16 plans, and under the paper's product-LUT
kernel at w2a2, w2a2 with group-64 scales and w4a8. The packed phases must
dispatch their weight kernel on the ``pallas`` backend only, and the
prompt logits of every phase but bf16 and w4a16 through the Pallas kernels
must match the ``ref`` route of the same packed tree within logit_tol.

Four chips: bf16 and w2a16 served by ``Engine(mesh=make_tp_mesh(4))`` and
by one chip, on the same traffic. Prompt logits must match within
logit_tol. The one-chip token streams are then fed back through the
tensor-parallel engine (teacher forcing), and its paged decode logits must
match the one-chip decode logits within logit_tol at every step. Per-device
weight bytes must be about a quarter of the replicated footprint, and
engine state must be spread evenly over the devices. The free-running
greedy token match share is printed, with the step where each stream first
diverges and the one-chip top-2 logit gap there.

Each phase prints one JSON line of set-up facts (compile and wall seconds,
requests, tokens, kernel dispatches, device kind, and the peak bytes
device 0 has held since the process started). The
last line is ``{"ok": true, "device": {...}}``. Everything runs in this one
process; without a TPU, or when any check fails, the script exits non-zero
and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
N_REQUESTS = 8
PROMPT_LENS = (64, 256)
GEN = 32
# Prompt logits are compared as max|diff| / max|logit|. Both routes (and
# both TP layouts) multiply the same bf16-exact operands with f32
# accumulation, so they differ only in summation order (~1e-6 relative per
# dense output), but the residual stream is bf16: such a difference can
# flip a bf16 rounding (2^-8 relative). The stream is rounded in series
# twice per layer (after attention and after the MLP) plus at the embedding
# and the final norm, so (2 L + 2) * 2^-8 bounds the drift when every flip
# errs the same way. A wrong kernel is off by O(1).
def logit_tol(n_layers: int) -> float:
    return (2 * n_layers + 2) * 2.0 ** -8


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu(n_chips: int):
    """The TPU devices, or exit non-zero naming the platform found."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found: JAX platform is {platform!r} "
                 f"({len(devices)} device(s)); this check runs on the chip "
                 "only")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU "
                 f"devices, found {len(devices)}")
    return devices


def serve_args(*flags: str):
    from repro.launch import serve

    return serve.build_parser().parse_args(
        ["--arch", ARCH, "--paged", "--batch", str(N_REQUESTS),
         "--requests", str(N_REQUESTS), "--prompt-len",
         str(PROMPT_LENS[1]), "--gen", str(GEN), *flags])


def make_prompts(vocab: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]


def dispatch_counts(counters: dict) -> dict:
    """kernel_dispatch_total counters summed as {"op:backend": n}."""
    out: dict[str, int] = {}
    for key, v in counters.items():
        if not key.startswith("kernel_dispatch_total{"):
            continue
        labels = dict(p.split("=", 1)
                      for p in key[key.index("{") + 1:-1].split(","))
        k = f"{labels['op']}:{labels['backend']}"
        out[k] = out.get(k, 0) + int(v)
    return out


def bytes_by_device(tree) -> dict:
    import jax

    out: dict[int, int] = {}
    for x in jax.tree.leaves(tree):
        for s in getattr(x, "addressable_shards", ()):
            out[s.device.id] = out.get(s.device.id, 0) + s.data.nbytes
    return out


def prompt_logits(cfg, params, tokens: np.ndarray, ctx=contextlib.nullcontext):
    """(P, V) f32 logits of one full-prompt forward (no cache)."""
    import jax
    from repro.models import lm
    from repro.obs import metrics as obs_metrics

    def fwd(p, t):
        with ctx():
            h, _ = lm.forward(p, cfg, t)
            return lm.logits_fn(p, cfg, h)

    with obs_metrics.scoped(isolate=True):
        out = jax.jit(fwd)(params, tokens[None])
    return np.asarray(out[0], np.float32)


def compare_logits(got: np.ndarray, want: np.ndarray, n_layers: int,
                   what: str) -> dict:
    check(got.shape == want.shape and np.isfinite(got).all()
          and np.isfinite(want).all(), f"{what}: non-finite or misshapen "
          f"logits {got.shape} vs {want.shape}")
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    tol = logit_tol(n_layers)
    res = {"check": what, "max_rel_diff": rel, "tolerance": tol,
           "rms_rel_diff": float(np.sqrt(((got - want) ** 2).mean()
                                         / (want ** 2).mean())),
           "argmax_agree": float((got.argmax(-1) == want.argmax(-1)).mean())}
    print(json.dumps(res), flush=True)
    check(rel <= tol, f"{what}: max|diff|/max|logit| {rel} > {tol}")
    return res


def decode_recorder(engine, forced=None):
    """A host-side sample hook for ``engine`` that keeps each decode step's
    logits per request uid, and returns the greedy tokens or, with
    ``forced`` ({uid: tokens}), the forced ones (teacher forcing)."""
    from repro.serving import engine as engine_mod

    steps: dict[int, list[np.ndarray]] = {}

    def hook(logits):
        lg = np.asarray(logits, np.float32)
        nxt = lg.argmax(-1).astype(np.int32)
        for i, s in enumerate(engine.slots):
            if s.state != engine_mod._DECODE:
                continue
            steps.setdefault(s.req.uid, []).append(lg[i])
            if forced is not None:
                nxt[i] = forced[s.req.uid][len(s.req.out)]
        return nxt

    return hook, steps


def run_requests(engine, prompts, gen: int, label: str):
    """Submit one request per prompt (uid = its index) and run the engine
    until all are done. Returns (requests, wall seconds, engine metrics)."""
    from repro.serving import Request

    reqs = [Request(uid=i, prompt=p, max_new=gen)
            for i, p in enumerate(prompts)]
    for r in reqs:
        check(engine.submit(r), f"{label}: request {r.uid} rejected")
    t0 = time.perf_counter()
    m = engine.run()
    return reqs, time.perf_counter() - t0, m


def serve_phase(label: str, base_cfg, params, prompts, flags=(), tp=1,
                record=False):
    """Pack under the plan the serve.py ``flags`` select, serve ``prompts``
    through make_engine, check every request completed, print the phase
    line. Returns (engine, per-request output tokens, and with ``record``
    the per-request decode logits)."""
    import jax
    from repro.launch import serve
    from repro.launch.mesh import make_tp_mesh
    from repro.models import lm

    args = serve_args(*flags, "--tp", str(tp))
    serve.validate_args(args, base_cfg)
    quant, desc = serve.plan_for(args)
    cfg = dataclasses.replace(base_cfg, quant=quant)
    t0 = time.perf_counter()
    qparams = jax.block_until_ready(lm.quantize_tree(params, cfg, tp=tp))
    pack_s = time.perf_counter() - t0
    engine = serve.make_engine(cfg, qparams, args,
                               mesh=make_tp_mesh(tp) if tp > 1 else None)
    steps = None
    if record:
        engine.sample, steps = decode_recorder(engine)
    reqs, wall_s, m = run_requests(engine, prompts, args.gen, label)
    hists = m["metrics"]["histograms"]
    compile_s = sum(h["count"] * h["mean"] for k, h in hists.items()
                    if k.startswith("jit_compile_s"))
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    done = [r for r in reqs if r.done]
    report = {
        "phase": label, "plan": desc, "arch": cfg.name,
        "n_layers": cfg.n_layers, "d_model": cfg.d_model,
        "vocab": cfg.vocab_size, "tp": tp, "pack_s": pack_s,
        "compile_s": compile_s, "wall_s": wall_s,
        "requests_completed": len(done), "requests": len(reqs),
        "tokens": sum(len(r.out) for r in done),
        "dispatch": dispatch_counts(m["metrics"]["counters"]),
        "device_kind": dev.device_kind,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }
    print(json.dumps(report), flush=True)
    check(len(done) == len(reqs), f"{label}: {len(done)}/{len(reqs)} done")
    check(all(len(r.out) == args.gen and min(r.out) >= 0
              and max(r.out) < cfg.vocab_size for r in reqs),
          f"{label}: wrong token counts or ids")
    return engine, [list(r.out) for r in reqs], steps


def check_pallas_only(engine, op: str, label: str) -> None:
    """``op`` dispatched on the pallas backend, and nothing on
    pallas_interpret or ref."""
    counts = dispatch_counts(engine.metrics()["metrics"]["counters"])
    check(counts.get(f"{op}:pallas", 0) > 0,
          f"{label}: no {op} dispatch on pallas: {counts}")
    bad = [k for k in counts
           if k.endswith(":pallas_interpret") or k.endswith(":ref")]
    check(not bad, f"{label}: non-chip dispatches {bad}")


# one-chip phases: (plan, serve.py flags, weight kernel, compare with ref)
ONE_CHIP = (("bf16", ("--plan", "bf16"), None, False),
            ("w2a16", (), "dequant_matmul", True),
            ("w4a16", ("--plan", "w4a16"), "dequant_matmul", False),
            ("w2a2", ("--plan", "w2a2"), "lut_gemm", True),
            ("w2a2g64", ("--plan", "w2a2g64"), "lut_gemm", True),
            ("w4a8", ("--plan", "w4a8"), "lut_gemm", True))


def one_chip(cfg, params, prompts) -> None:
    for plan, flags, op, vs_ref in ONE_CHIP:
        engine, _, _ = serve_phase(plan, cfg, params, prompts, flags)
        if op is not None:
            check_pallas_only(engine, op, plan)
        if vs_ref:
            ref_cfg = dataclasses.replace(engine.cfg, quant=dataclasses.replace(
                engine.cfg.quant, backend="ref"))
            compare_logits(prompt_logits(engine.cfg, engine.params, prompts[0]),
                           prompt_logits(ref_cfg, engine.params, prompts[0]),
                           cfg.n_layers, f"{plan} pallas vs ref prompt logits")
        del engine


def divergences(out_tp, out_1, steps_tp, steps_1) -> list[dict]:
    """For each free-running stream that leaves the one-chip one: the first
    differing step, the one-chip top-2 logit gap there, and the largest
    teacher-forced logit difference in that row. A gap below that
    difference is a near-tie that rounding may flip."""
    out = []
    for uid, (x, y) in enumerate(zip(out_tp, out_1)):
        t = next((t for t, (a, b) in enumerate(zip(x, y)) if a != b), None)
        if t is None:
            continue
        top2 = np.sort(steps_1[uid][t])[-2:]
        out.append({"uid": uid, "step": t,
                    "tp1_top2_gap": float(top2[1] - top2[0]),
                    "row_max_abs_diff": float(np.abs(
                        steps_tp[uid][t] - steps_1[uid][t]).max())})
    return out


def four_chips(cfg, params, prompts, n: int = 4) -> None:
    for plan, flags in (("bf16", ("--plan", "bf16")), ("w2a16", ())):
        e_1, out_1, steps_1 = serve_phase(f"{plan} tp1", cfg, params,
                                          prompts, flags, record=True)
        lg_1 = prompt_logits(e_1.cfg, e_1.params, prompts[0])
        w_1 = e_1.per_device_weight_bytes()
        del e_1
        e_tp, out_tp, _ = serve_phase(f"{plan} tp{n}", cfg, params, prompts,
                                      flags, tp=n)
        if plan != "bf16":
            check_pallas_only(e_tp, "dequant_matmul", f"{plan} tp{n}")
        per_dev = bytes_by_device((e_tp.params, e_tp.caches))
        lg_tp = prompt_logits(e_tp.cfg, e_tp.params, prompts[0],
                              e_tp._mesh_ctx)
        w_tp = e_tp.per_device_weight_bytes()
        e_tp.sample, steps_tp = decode_recorder(
            e_tp, forced=dict(enumerate(out_1)))
        forced, _, _ = run_requests(e_tp, prompts, GEN, f"{plan} tp{n} forced")
        del e_tp
        check(all(r.done and list(r.out) == out_1[r.uid]
                  and len(steps_tp[r.uid]) == GEN for r in forced),
              f"{plan} tp{n}: teacher-forced run did not replay the streams")
        pairs = [(a, b) for x, y in zip(out_tp, out_1) for a, b in zip(x, y)]
        ratio = w_tp / w_1
        print(json.dumps({
            "check": f"{plan} tp{n} vs tp1",
            "greedy_token_match": sum(a == b for a, b in pairs) / len(pairs),
            "first_divergences": divergences(out_tp, out_1, steps_tp,
                                             steps_1),
            "per_device_weight_bytes": w_tp, "replicated_weight_bytes": w_1,
            "weight_ratio": ratio, "engine_bytes_by_device": per_dev,
        }), flush=True)
        compare_logits(lg_tp, lg_1, cfg.n_layers,
                       f"{plan} tp{n} vs tp1 prompt logits")
        uids = range(len(prompts))
        compare_logits(np.stack([s for u in uids for s in steps_tp[u]]),
                       np.stack([s for u in uids for s in steps_1[u]]),
                       cfg.n_layers,
                       f"{plan} tp{n} vs tp1 teacher-forced decode logits")
        check(abs(ratio - 1 / n) <= 0.05,
              f"{plan}: per-device weights {ratio:.3f} of replicated")
        check(len(per_dev) == n
              and max(per_dev.values()) <= 1.1 * min(per_dev.values()),
              f"{plan}: engine state unevenly placed {per_dev}")


def run(cfg, chips: int, seed: int) -> None:
    import jax
    from repro.models import lm

    params = lm.init_params(jax.random.PRNGKey(seed), cfg, mode="plain")
    prompts = make_prompts(cfg.vocab_size, seed)
    if chips == 1:
        one_chip(cfg, params, prompts)
    else:
        four_chips(cfg, params, prompts, chips)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the tensor-parallel path and its one-chip "
                         "comparison only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    from repro.configs import get_config
    from repro.launch import compile_cache

    compile_cache.enable()
    try:
        run(get_config(ARCH), args.chips, args.seed)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
