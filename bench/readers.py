"""The arithmetic behind the per-layer metric readers in
``bench/layer_metrics``: each reader file names its quantity and calls one
of these on the run's ``harness.RunView``. A reader that finds nothing to
read returns None, and the metric is left out of the line.
"""

from __future__ import annotations

import re
import sys

from bench.work import model_flops, packed_gemm

# device operations of each packed weight op, found by their HLO text and
# not by name: a Pallas kernel is a custom call with the target
# "tpu_custom_call", and the packed weight kernels alone take a uint8
# operand (the packed codes); the instruction's name follows the jitted
# wrapper or the pallas_call's ``name=`` and may change
PACKED_OP_EVENTS = {
    "dequant_matmul":
        r'custom-call\(.*?\bu8\[.*custom_call_target="tpu_custom_call"'}
MIN_TRACED = 0.5   # least share of an executable's kernel calls traced


def decode_step_ms(run):
    """Device time of the decode executable per decode step, in ms."""
    return _step_ms(run, run.decode_module, "decode_steps")


def prefill_step_ms(run):
    """Device time of the prefill executable per prefill step, in ms."""
    return _step_ms(run, run.prefill_module, "prefill_steps")


def _step_ms(run, module: str, counter: str):
    if run.trace is None or not run.counters[counter]:
        return None
    t, n = run.trace.module_time(module)
    return 1e3 * t / run.counters[counter] if n else None


def idle_share(run):
    """Percent of the traced window with no operation on the device."""
    return None if run.trace is None else 100.0 * run.trace.idle_share


def mfu(run):
    """Model operations of every token the traced window processed, over
    the window and the chip's bf16 peak, in percent."""
    if run.trace is None:
        return None
    cfg = run.config
    ops = sum(model_flops.decode_ops(cfg, c) for c in run.decode_contexts)
    ops += sum(model_flops.prompt_ops(cfg, p, n)
               for p, n in run.prompt_segments)
    ops += run.prompt_heads * model_flops.head_ops(cfg)
    if ops <= 0:
        return None
    return 100.0 * ops / (run.trace.window_s * run.peaks["bf16_flops"])


def packed_gemm_roofline(run):
    """The packed linears' least time at the chip's peaks over their device
    time, in percent. Every decode step runs each linear once over
    ``decode_rows`` rows, every prefill step once over ``prefill_rows``.

    The device time is that of the events of the op the dispatch counters
    name, taken executable by executable. The profiler may keep fewer
    events than ran, with no mark in the trace (seen on a v5e: a decode
    run's kernel events about 1% short, its busy and executable times
    whole); an executable's time is then its traced calls' mean times its calls,
    where at least MIN_TRACED of them were traced. More events than calls,
    or events outside both step executables, leave the metric unread."""
    if run.trace is None:
        return None
    op = run.config["packed_op"]
    if not any(k.startswith(op + ":") for k in run.dispatch):
        return None
    hits = run.trace.op_hits(PACKED_OP_EVENTS[op])
    q = run.config["quant"]
    shapes = packed_gemm.layer_shapes(run.config)
    L = run.config["num_hidden_layers"]
    t = least = 0.0
    placed = 0
    for module, steps, M in (
            (run.decode_module, run.counters["decode_steps"], run.decode_rows),
            (run.prefill_module, run.counters["prefill_steps"],
             run.prefill_rows)):
        mine = [v for k, v in hits.items()
                if re.search(module, k.split("/", 1)[0])]
        placed += len(mine)
        s, n = sum(v[0] for v in mine), sum(v[1] for v in mine)
        calls = steps * L * len(shapes)
        if n > calls or n < MIN_TRACED * calls:
            print(f"packed_gemm_roofline: {n} events of {op} in {module}, "
                  f"{calls} calls: not read", file=sys.stderr)
            return None
        if not calls:
            continue
        if n < calls:
            print(f"packed_gemm_roofline: {n} of {calls} calls of {op} in "
                  f"{module} traced: their mean time taken for all",
                  file=sys.stderr)
        t += s * calls / n
        least += steps * L * sum(packed_gemm.roofline_s(
            M, K, N, run.peaks, w_bits=q["weight_bits"], a_bits=q["act_bits"])
            for K, N in shapes)
    if placed != len(hits):
        print(f"packed_gemm_roofline: events of {op} outside the step "
              "executables: not read", file=sys.stderr)
        return None
    return 100.0 * least / t if t > 0 else None
