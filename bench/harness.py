"""One run of one cell: build the served model from the seed, drive it with
the cell's traffic for a measured window, check what it served against the
plain reference, and reduce it all to the result line.

From the program it takes the system under test through its serving entry
points only: ``repro.launch.serve`` (``build_parser``, ``validate_args``,
``plan_for``), the fixed-batch step functions
``repro.launch.steps.make_prefill_step`` / ``make_decode_step`` that
``serve`` runs without ``--paged``, ``repro.models.lm.quantize_tree`` and
``repro.configs.get_config``; and it reads the program's kernel dispatch
counters.

The loop it drives is serve.py's fixed-batch loop (the mix's ``loop`` is
``"batch"``): one prefill of ``slots`` prompts of one length, then one
decode step per further token, greedy, every step dispatched before the
batch's tokens are read. Batches run back to back.

Timeline of a run (host clock, ``time.perf_counter``):

  set-up   process start -> weights -> pack -> warm-up (compiles or loads
           every program the traffic uses)
  window   whole batches: it closes with the first batch to end past
           ``seconds`` (every batch is the same work); with ``trace`` the profiler
           records TRACE_SEGMENTS stretches of TRACE_STEPS steps of the
           window's first batch, spread evenly over it from the prefill to
           the last decode step, the device drained at both ends of each
  check    program state freed, then the reference over a sample
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import check, traffic as traffic_mod, trace_reduce, weights as W

LAYER_METRICS = Path(__file__).resolve().parent / "layer_metrics"
TRACE_SEGMENTS = 8      # traced stretches of the window's first batch
TRACE_STEPS = 1         # steps (the prefill counts as one) in each stretch
now = time.perf_counter


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader sees of a traced run."""
    config: dict
    peaks: dict
    trace: trace_reduce.Reduced | None
    counters: dict            # traced steps: decode_steps, prefill_steps
    dispatch: dict            # {"op:backend": n}
    decode_rows: int          # rows of every decode step (the slots)
    prefill_rows: int         # rows of every prefill step
    decode_module: str        # regex of the decode executable's name
    prefill_module: str       # regex of the prefill executable's name
    decode_contexts: list     # context of each token decoded while traced
    prompt_segments: list     # (first position, tokens) prefilled while traced
    prompt_heads: int = 0     # prefill rows whose logits were computed


@dataclasses.dataclass
class Outcome:
    """What the batch loop hands back: the window's facts and what was served."""
    setup_s: float
    window_s: float
    values: dict              # end-to-end metric name -> value
    attempted: int
    failed: int
    finished: list            # check.Served of every finished request
    dispatch: dict
    compiles_in_window: int
    device: dict
    max_len: int              # longest context the server was sized for
    view: RunView | None = None


def _load_reader(name: str):
    path = LAYER_METRICS / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _program(config: dict, mix: dict, program_cfg=None, backend=None):
    """The program config of a configuration under a mix, as ``serve``
    would build it."""
    import dataclasses as dc

    from repro.configs import get_config
    from repro.launch import serve

    cfg = program_cfg if program_cfg is not None else get_config(config["arch"])
    want = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
            "n_heads": "num_attention_heads", "n_kv_heads":
            "num_key_value_heads", "d_ff": "intermediate_size",
            "vocab_size": "vocab_size", "rope_theta": "rope_theta",
            "tie_embeddings": "tie_word_embeddings"}
    diff = {k: (getattr(cfg, k), config[v]) for k, v in want.items()
            if getattr(cfg, k) != config[v]}
    if diff:
        raise ValueError(f"program config {cfg.name} differs from "
                         f"{config['name']}: {diff}")
    p_max, g_max = traffic_mod.max_lengths(mix)
    args = serve.build_parser().parse_args(
        ["--arch", config["arch"], *config["serve_flags"],
         "--batch", str(mix["slots"]),
         "--prompt-len", str(p_max), "--gen", str(g_max)])
    serve.validate_args(args, cfg)
    quant, _ = serve.plan_for(args)
    if backend is not None:
        quant = dc.replace(quant, backend=backend)
    return dc.replace(cfg, quant=quant)


def _packed(config: dict, cfg, seed: int, t_start: float):
    """The configuration's weights made from the seed and packed under the
    plan by ``lm.quantize_tree``; prints when each part of set-up ended."""
    import jax

    from repro.models import lm

    weights = jax.block_until_ready(W.make_weights(config, seed))
    t1 = now()
    qparams = jax.block_until_ready(lm.quantize_tree(weights, cfg))
    print(f"set-up: weights made at {t1 - t_start:.3f} s, packed at "
          f"{now() - t_start:.3f} s", file=sys.stderr)
    return qparams


class _Builds:
    """Executables built in this process since it was made (compiled, or
    loaded from the persistent cache), eager operations' included, counted
    from JAX's monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1


def _device_info(devices) -> dict:
    d = devices[0]
    peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dv in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


class _Tracer:
    """The profiler over part of the window, marked by the benchmark's
    window span (trace_reduce.WINDOW_SPAN); the trace is read and deleted
    after the run."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self) -> None:
        import jax
        from jax.profiler import TraceAnnotation

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.ann = TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self.ann.__enter__()

    def stop(self) -> None:
        import jax

        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> trace_reduce.Reduced:
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)
            return trace_reduce.reduce(sorted(path)[-1])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# --------------------------------------------------------------------------
# the fixed-batch loop
# --------------------------------------------------------------------------

def _reduce_all(tracers: list) -> trace_reduce.Reduced:
    """The traced stretches of a run as one reduced trace; every trace file
    is deleted, also when one is refused."""
    try:
        return trace_reduce.combine([x.reduce() for x in tracers])
    finally:
        for x in tracers:
            shutil.rmtree(x.dir, ignore_errors=True)


def trace_stretches(steps: int, n: int = TRACE_SEGMENTS,
                    length: int = TRACE_STEPS) -> list:
    """The traced stretches of a batch of ``steps`` steps (step 0 is the
    prefill, step i > 0 the decode step at context P + i - 1) as
    [(first, last)]: ``n`` stretches of ``length`` steps spread evenly from
    the first step to the last, merged where they touch."""
    length = min(length, steps)
    starts = sorted({round(j * (steps - length) / max(n - 1, 1))
                     for j in range(n)})
    out: list = []
    for s in starts:
        if out and s <= out[-1][1] + 1:
            out[-1][1] = max(out[-1][1], s + length - 1)
        else:
            out.append([s, s + length - 1])
    return [tuple(x) for x in out]


def _drive_batch(cell, config, mix, *, seed, seconds, trace, peaks, t_start,
                 program_cfg, backend, fault, builds) -> Outcome:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.launch import steps as St
    from repro.obs import metrics as obs_metrics

    for k in ("prompt", "output"):
        if mix[k]["min"] != mix[k]["max"]:
            raise ValueError(f"a batch mix has one {k} length: {mix[k]}")
    cfg = _program(config, mix, program_cfg, backend)
    B, (P, G) = int(mix["slots"]), traffic_mod.max_lengths(mix)
    qparams = _packed(config, cfg, seed, t_start)
    prefill = jax.jit(St.make_prefill_step(cfg, max_len=P + G))
    decode = jax.jit(St.make_decode_step(cfg), donate_argnums=(1,))
    reg = obs_metrics.MetricsRegistry()
    pos = [jnp.full((B,), P + i, jnp.int32) for i in range(G - 1)]
    step = decode if fault is None else fault(decode)
    stretches = trace_stretches(G) if trace else []
    starts = {a for a, _ in stretches}
    ends = {b for _, b in stretches}
    tracers: list = []

    def generate(tokens, steps=G, traced=False):
        """One batch as serve's fixed-batch loop runs it: (B, steps) tokens.
        ``traced``: the profiler records the stretches of ``stretches``."""
        with obs_metrics.scoped(registry=reg), TraceAnnotation("bench.batch"):
            out = []
            for i in range(steps):
                if traced and i in starts:
                    if out:
                        jax.block_until_ready(out[-1])
                    tracers.append(_Tracer())
                    tracers[-1].start()
                if i == 0:
                    logits, caches = prefill(qparams, {"tokens": tokens})
                else:
                    logits, caches = step(qparams, caches,
                                          {"tokens": out[-1][:, None],
                                           "pos": pos[i - 1]})
                out.append(jnp.argmax(logits[:, -1], -1))
                if traced and i in ends:
                    jax.block_until_ready(out[-1])
                    tracers[-1].stop()
            return np.asarray(jnp.stack(out, 1))

    tr = traffic_mod.Traffic(mix, seed, config["vocab_size"])
    batches: list = []

    def one_batch(traced=False):
        k = len(batches)
        reqs = [tr.request(k * B + j) for j in range(B)]
        toks = generate(jnp.asarray(np.stack([r.prompt for r in reqs])),
                        traced=traced)
        batches.append((reqs, toks))

    # warm-up: compiles both steps, the eager ops between them and the final
    # stack of all G tokens
    generate(jnp.zeros((B, P), jnp.int32), steps=2)
    jnp.stack([jnp.zeros((B,), jnp.int32)] * G, 1).block_until_ready()
    print(f"set-up: warm-up done at {now() - t_start:.3f} s", file=sys.stderr)
    b0, w0 = builds.n, now()
    while True:
        one_batch(traced=trace and not batches)
        if now() >= w0 + seconds:
            break
    w1, n, b1 = now(), len(batches), builds.n

    view = None
    dispatch = check.dispatch_counts(reg.snapshot()["counters"])
    if trace:
        dec = [i for a, b in stretches for i in range(max(a, 1), b + 1)]
        pre = int(stretches[0][0] == 0)
        view = RunView(
            config=config, peaks=peaks,
            trace=_reduce_all(tracers),
            counters={"decode_steps": len(dec), "prefill_steps": pre},
            dispatch=dispatch, decode_rows=B, prefill_rows=B * P,
            decode_module=r"^jit_decode_step$",
            prefill_module=r"^jit_prefill_step$",
            decode_contexts=[P + i - 1 for i in dec] * B,
            prompt_segments=[(0, P)] * (B * pre), prompt_heads=B * pre)
    out = Outcome(
        setup_s=w0 - t_start, window_s=w1 - w0, values={},
        attempted=n * B, failed=0,
        finished=[check.Served(r.uid, r.prompt, list(row))
                  for reqs, toks in batches for r, row in zip(reqs, toks)],
        dispatch=dispatch, compiles_in_window=b1 - b0,
        device=_device_info(jax.devices()[:int(cell["chips"])]),
        max_len=P + G, view=view)
    del qparams, prefill, decode, step
    gc.collect()
    out.values = {"output_tok_s": n * B * G / out.window_s,
                  "prompt_tok_s": n * B * P / out.window_s}
    print(f"window {out.window_s:.3f}s: {n} batches of {B} x "
          f"({P} + {G}) tokens", file=sys.stderr)
    return out


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run(cell: dict, config: dict, mix: dict, metrics: dict, *, seed: int,
        seconds: float, trace: bool, peaks: dict, t_start: float,
        program_cfg=None, backend=None, fault=None) -> dict:
    """Run one cell and return the result line's object. ``metrics`` holds
    the cell's end-to-end and per-layer metric entries of BENCHMARK.json.
    ``program_cfg``, ``backend`` and ``fault`` serve the tests: a smaller
    program configuration, another kernel backend, and a hook that may
    break the served program (it gets the fixed-batch loop's jitted decode
    step and returns its replacement)."""
    if mix["loop"] not in traffic_mod.LOOPS:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    out = _drive_batch(cell, config, mix, seed=seed, seconds=seconds, trace=trace,
                peaks=peaks, t_start=t_start, program_cfg=program_cfg,
                backend=backend, fault=fault, builds=_Builds())

    checks = check.dispatch_checks(out.dispatch, config["packed_op"])
    checks["compiles_in_window"] = {"value": out.compiles_in_window,
                                    "limit": 0}
    sample = check.sample(out.finished, seed)
    ref = check.load_reference(config)
    ref_weights = W.make_weights(config, seed)
    g = check.gaps(ref, ref_weights, config, sample,
                   length=-(-out.max_len // ref.Q_BLOCK) * ref.Q_BLOCK,
                   n_rows=traffic_mod.max_lengths(mix)[1])
    del ref_weights
    checks["logit_gap"] = {
        "value": max((float(x.max()) for x in g["served"]), default=math.inf),
        "limit": config.get("logit_gap_limits", {}).get(cell["traffic"], 0.0)}
    checks["sampled_tokens"] = {"value": sum(len(s.out) for s in sample),
                                "limit": 1, "at_least": True}
    correct = all(c["value"] >= c["limit"] if c.get("at_least")
                  else c["value"] <= c["limit"] for c in checks.values())

    values = dict(out.values, setup_s=out.setup_s)
    result_metrics = {}
    if not trace:
        for entry in metrics["end_to_end"]:
            v = values.get(entry["name"])
            if v is not None:
                result_metrics[entry["name"]] = {"value": v,
                                                 "unit": entry["unit"]}
    result = {"correct": bool(correct), "attempted": out.attempted,
              "failed": out.failed, "metrics": result_metrics,
              "device": out.device}
    if trace:
        red = out.view.trace
        for entry in metrics["per_layer"]:
            v = _load_reader(entry["name"])(out.view)
            if v is not None:
                result_metrics[entry["name"]] = {"value": float(v),
                                                 "unit": entry["unit"]}
        out.device["busy_s"] = red.busy_s
        out.device["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.top_gaps(10)}
    result["checks"] = checks
    print(f"compiles in window {out.compiles_in_window}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} "
              f"{'>=' if c.get('at_least') else '<='} limit {c['limit']!r}",
              file=sys.stderr)
    return result
