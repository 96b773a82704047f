"""The arithmetic behind the per-layer metric readers in
``bench/layer_metrics``: each reader file names its quantity and calls one
of these on the run's ``harness.RunView``. A reader that finds nothing to
read returns None, and the metric is left out of the line.
"""

from __future__ import annotations

import re
import sys

from bench import configfile
from bench.work import packed_gemm

# device operations of each packed weight op, found by their HLO text and
# not by name: a Pallas kernel is a custom call with the target
# "tpu_custom_call", and the packed weight kernels alone take a uint8
# operand (the packed codes); the instruction's name follows the jitted
# wrapper or the pallas_call's ``name=`` and may change. The pattern matches
# every packed kernel: a configuration that runs two of them gives its
# readers narrower patterns
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
    the window and the chip's bf16 peak, in percent. The operations come
    from the configuration's ``work`` module."""
    if run.trace is None:
        return None
    cfg = run.config
    work = configfile.module(cfg, "work")
    ops = sum(work.decode_ops(cfg, c) for c in run.decode_contexts)
    ops += sum(work.prompt_ops(cfg, p, n) for p, n in run.prompt_segments)
    ops += run.prompt_heads * work.head_ops(cfg)
    if ops <= 0:
        return None
    return 100.0 * ops / (run.trace.window_s * run.peaks["bf16_flops"])


def op_time(run, events: str, calls: int):
    """Device time of the events whose HLO text matches ``events``, over
    both step executables, where each decode step makes ``calls`` calls of
    the op and each prefill step as many.

    The time is taken executable by executable. The profiler may keep fewer
    events than ran, with no mark in the trace (seen on a v5e: a decode
    run's kernel events about 1% short, its busy and executable times
    whole); an executable's time is then its traced calls' mean times its
    calls, where at least MIN_TRACED of them were traced. More events than
    calls, or events outside both step executables, give None."""
    hits = run.trace.op_hits(events)
    t, placed = 0.0, 0
    for module, steps in ((run.decode_module, run.counters["decode_steps"]),
                          (run.prefill_module,
                           run.counters["prefill_steps"])):
        mine = [v for k, v in hits.items()
                if re.search(module, k.split("/", 1)[0])]
        placed += len(mine)
        s, n = sum(v[0] for v in mine), sum(v[1] for v in mine)
        want = steps * calls
        if n > want or n < MIN_TRACED * want:
            print(f"op_time: {n} events of {events!r} in {module}, {want} "
                  "calls: not read", file=sys.stderr)
            return None
        if not want:
            continue
        if n < want:
            print(f"op_time: {n} of {want} calls in {module} traced: their "
                  "mean time taken for all", file=sys.stderr)
        t += s * want / n
    if placed != len(hits):
        print(f"op_time: events of {events!r} outside the step executables: "
              "not read", file=sys.stderr)
        return None
    return t


def packed_gemm_roofline(run, events=None):
    """The dense packed linears' least time at the chip's peaks over their
    device time, in percent. The linears are the configuration's ``work``
    module's ``dense_calls``, run by its first packed op: every decode step
    runs each of them over ``decode_rows`` rows, every prefill step over
    ``prefill_rows``.

    ``events`` is the pattern of the op's events' HLO text
    (``PACKED_OP_EVENTS[op]`` by default; a configuration whose other
    packed ops would match it gives a narrower one). The device time is
    ``op_time``'s; where it gives None, or the op was not dispatched, the
    metric is left unread."""
    if run.trace is None:
        return None
    op = configfile.packed_ops(run.config)[0]
    if not any(k.startswith(op + ":") for k in run.dispatch):
        return None
    calls = configfile.module(run.config, "work").dense_calls(run.config)
    t = op_time(run, events or PACKED_OP_EVENTS[op],
                sum(c for _, _, c in calls))
    if not t:
        return None
    q = run.config["quant"]
    least = sum(steps * c * packed_gemm.roofline_s(
        M, K, N, run.peaks, w_bits=q["weight_bits"], a_bits=q["act_bits"])
        for steps, M in ((run.counters["decode_steps"], run.decode_rows),
                         (run.counters["prefill_steps"], run.prefill_rows))
        for K, N, c in calls)
    return 100.0 * least / t
