"""Reduction of a ``jax.profiler`` trace to the numbers the per-layer metrics
read: device busy time, device time per executable and per operation, and
the device's idle gaps with what the host was doing in each.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes,
read with ``jax.profiler.ProfileData``. On a TPU v5e (read by hand from
one chip trace) device planes are named ``/device:TPU:<n>``; on each, the
line ``XLA Modules`` holds one event per executable run, named after the
jitted function and a fingerprint (``jit__decode_fn(8952784744762590545)``),
and the line ``XLA Ops`` one event per operation run, named by its HLO
text (``%dequant_matmul_pallas.21 = f32[256,256]... custom-call(...)``: a
Pallas kernel is a custom call named after the jitted function that
wraps its ``pallas_call``). A loop's ``while`` op spans the ops of its
body on the same line, so operations nest; the reduction keeps each op's
self time. The line ``Async XLA Ops`` (DMA starts and waits) is not
counted as busy. The host plane ``/host:CPU`` holds the host threads'
spans, among them the benchmark's own ``jax.profiler.TraceAnnotation``
spans and the runtime's (``PjitFunction(...)``, ``np.asarray(jax.Array)``,
the host waiting on a result). Host and device events share one clock.

The window is the benchmark's span ``WINDOW_SPAN``; events are clipped to
it. When the profiler's buffers fill, it drops events and marks the trace
with an event ``Trace Buffers Dropped`` (seen on a v5e over a 14 s trace
of 383 decode steps, where 242 steps remained): such a trace is refused.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

WINDOW_SPAN = "bench.traced_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DROPPED = "Trace Buffers Dropped"  # the profiler's mark of a full buffer
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")


def base_name(name: str) -> str:
    """An event name without its instance suffix: ``fusion.12`` ->
    ``fusion``, ``jit__decode_fn(3)`` -> ``jit__decode_fn``."""
    return _SUFFIX.sub("", name)


def op_name(hlo_text: str) -> str:
    """The instruction name of an ``XLA Ops`` event: ``%fusion.3 = f32[..]
    fusion(..)`` -> ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Reduced:
    """What one traced window shows, averaged over the device planes."""
    window_s: float
    busy_s: float                     # union of operation intervals
    n_devices: int
    ops: dict                         # "module/op.N" -> [self seconds, count]
    texts: dict                       # "module/op.N" -> the op's HLO text
    modules: dict                     # base module name -> [seconds, count]
    gaps: list                        # [(seconds, host span name)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_hits(self, pattern: str) -> dict:
        """{"module/op.N": [self seconds, count]} of the operations whose
        HLO text (``%name = type op(operands), attributes``) the regular
        expression ``pattern`` finds."""
        rx = re.compile(pattern)
        return {k: v for k, v in self.ops.items() if rx.search(self.texts[k])}

    def op_time(self, pattern: str) -> tuple[float, int]:
        """(self seconds, count) of the operations ``op_hits`` finds."""
        hits = self.op_hits(pattern).values()
        return sum(h[0] for h in hits), sum(h[1] for h in hits)

    def module_time(self, pattern: str) -> tuple[float, int]:
        """(seconds, count) of the executables whose base name matches."""
        rx = re.compile(pattern)
        hits = [v for k, v in self.modules.items() if rx.search(k)]
        return sum(h[0] for h in hits), sum(h[1] for h in hits)

    def top_ops(self, n: int = 10) -> list:
        return [[k, v[0]] for k, v in sorted(
            self.ops.items(), key=lambda kv: -kv[1][0])[:n]]

    def top_gaps(self, n: int = 10) -> list:
        """The idle time summed by what the host was doing, largest first."""
        by: dict[str, float] = {}
        for s, name in self.gaps:
            by[name] = by.get(name, 0.0) + s
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])
                [:n]]


def _union(intervals: list) -> tuple[float, list]:
    """Total covered length of (start, end) intervals and the gaps between
    the merged runs."""
    total, gaps, cur = 0.0, [], None
    for s, e in sorted(intervals):
        if cur is None:
            cur = [s, e]
        elif s <= cur[1]:
            cur[1] = max(cur[1], e)
        else:
            total += cur[1] - cur[0]
            gaps.append((cur[1], s))
            cur = [s, e]
    if cur is not None:
        total += cur[1] - cur[0]
    return total, gaps


def _clip(s: float, e: float, w0: float, w1: float):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def _host_spans(host_planes) -> tuple[tuple | None, list]:
    """The window span's (start, end), and the spans of the host thread
    that ran it (the benchmark's loop) as (start, end, name)."""
    for plane in host_planes:
        for line in plane.lines:
            events = list(line.events)
            win = [ev for ev in events if ev.name == WINDOW_SPAN]
            if win:
                return ((win[0].start_ns, win[0].end_ns),
                        [(ev.start_ns, ev.end_ns, ev.name) for ev in events
                         if ev.duration_ns > 0 and ev.name != WINDOW_SPAN])
    return None, []


def _label(spans: list, t: float) -> str:
    """The shortest host span covering time t (the innermost work)."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return base_name(best[1]) if best else "no host span"


def reduce(path: str, *, min_gap_s: float = 0.0) -> Reduced:
    """Reduce the trace at ``path`` over the benchmark's window span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")
               and "SparseCore" not in p.name]
    hosts = [p for p in planes if p.name.startswith("/host:")]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane "
                         f"({[p.name for p in planes]})")
    dropped = [p.name for p in planes for line in p.lines
               for ev in line.events if ev.name == DROPPED]
    if dropped:
        raise ValueError(f"{path}: the profiler dropped events ({DROPPED!r} "
                         f"on {sorted(set(dropped))}): trace fewer steps")
    window, spans = _host_spans(hosts)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span on the host")
    w0, w1 = window
    ops: dict[str, list] = {}
    texts: dict[str, str] = {}
    modules: dict[str, list] = {}
    busy_total, gaps = 0.0, []
    for plane in devices:
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = []
        for ev in lines.get(MODULES_LINE, []):
            iv = _clip(ev.start_ns, ev.end_ns, w0, w1)
            if iv is None:
                continue
            name = base_name(ev.name)
            acc = modules.setdefault(name, [0.0, 0])
            acc[0] += (iv[1] - iv[0]) * 1e-9
            acc[1] += 1
            mods.append((ev.start_ns, ev.end_ns, name))
        mods.sort()
        starts = [m[0] for m in mods]
        intervals, stack = [], []      # stack: (end, key) of enclosing ops
        for ev in sorted(lines.get(OPS_LINE, []), key=lambda e: e.start_ns):
            iv = _clip(ev.start_ns, ev.end_ns, w0, w1)
            if iv is None:
                continue
            intervals.append(iv)
            while stack and stack[-1][0] <= iv[0]:
                stack.pop()
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= ev.start_ns \
                else "no module"
            key = f"{mod}/{op_name(ev.name)}"
            texts.setdefault(key, ev.name)
            acc = ops.setdefault(key, [0.0, 0])
            d = (iv[1] - iv[0]) * 1e-9
            acc[0] += d
            acc[1] += 1
            if stack:                  # nested: not the parent's own time
                ops[stack[-1][1]][0] -= d
            stack.append((iv[1], key))
        busy, g = _union(intervals)
        busy_total += busy
        gaps += [(e - s, s, e) for s, e in g]
    n = len(devices)
    host = sorted(spans)
    labelled = [((e - s) * 1e-9 / n, _label(host, (s + e) / 2))
                for d, s, e in gaps if d * 1e-9 >= min_gap_s]
    labelled.sort(key=lambda x: -x[0])
    scale = 1.0 / n
    for table in (ops, modules):
        for v in table.values():
            v[0] *= scale
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_total * 1e-9 / n,
                   n_devices=n, ops=ops, texts=texts, modules=modules,
                   gaps=labelled)


def combine(parts: list) -> Reduced:
    """Several traced windows of one run as one: times, counts and gaps
    added up."""
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    texts: dict[str, str] = {}
    for r in parts:
        for table, mine in ((r.ops, ops), (r.modules, modules)):
            for k, (s, c) in table.items():
                acc = mine.setdefault(k, [0.0, 0])
                acc[0] += s
                acc[1] += c
        for k, v in r.texts.items():
            texts.setdefault(k, v)
    return Reduced(window_s=sum(r.window_s for r in parts),
                   busy_s=sum(r.busy_s for r in parts),
                   n_devices=parts[0].n_devices, ops=ops, texts=texts,
                   modules=modules,
                   gaps=sorted((g for r in parts for g in r.gaps),
                               key=lambda x: -x[0]))
