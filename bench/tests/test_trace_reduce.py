"""The reduction from a profiler trace to the per-layer numbers, checked on
a small trace recorded on a TPU v5e (``data/v5e_decode.xplane.pb``: three
decode steps of a two-layer, 256-wide qwen1.5-shaped model packed at w2a16,
run by the same fixed-batch loop as the benchmark's cells, inside the
benchmark's window span) against a brute-force reading of the same file."""

from pathlib import Path

import pytest

from bench import readers, trace_reduce as T

TRACE = Path(__file__).resolve().parent / "data" / "v5e_decode.xplane.pb"


def test_union_and_gaps():
    total, gaps = T._union([(5, 7), (0, 2), (1, 3), (9, 10), (6, 8)])
    assert total == 3 + 3 + 1
    assert gaps == [(3, 5), (8, 9)]
    assert T._union([]) == (0.0, [])


def test_base_name():
    assert T.base_name("fusion.12") == "fusion"
    assert T.base_name("jit__decode_fn(3)") == "jit__decode_fn"
    assert T.base_name("copy-start.1.2") == "copy-start"
    assert T.base_name("jit_decode_step") == "jit_decode_step"
    assert T.op_name("%dequant_matmul_pallas.21 = f32[256,256]{1,0} "
                     "custom-call(bf16[4,256,64] %bitcast.139)") == \
        "dequant_matmul_pallas.21"


def test_label_picks_the_innermost_span():
    spans = sorted([(0, 100, "bench.batch"), (10, 20, "PjitFunction(f)"),
                    (50, 60, "bench.wait_for_arrival")])
    assert T._label(spans, 15) == "PjitFunction(f)"
    assert T._label(spans, 30) == "bench.batch"
    assert T._label(spans, 200) == "no host span"


def _brute(path):
    """Busy time and op totals of the device plane inside the window span,
    read event by event."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    w = [(e.start_ns, e.end_ns) for p in data.planes if p.name.startswith(
        "/host:") for line in p.lines for e in line.events
        if e.name == T.WINDOW_SPAN]
    assert len(w) == 1
    w0, w1 = w[0]
    dev = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    assert dev
    ops = [(max(e.start_ns, w0), min(e.end_ns, w1), e.name)
           for p in dev for line in p.lines if line.name == T.OPS_LINE
           for e in line.events if min(e.end_ns, w1) > max(e.start_ns, w0)]
    covered = set()
    for s, e, _ in ops:                      # 10 ns cells: coarse but exact
        covered.update(range(int(s) // 10, int(-(-e // 10))))
    return w1 - w0, len(covered) * 10, ops


def test_recorded_v5e_trace():
    red = T.reduce(str(TRACE))
    window_ns, busy_ns, ops = _brute(TRACE)
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(window_ns * 1e-9)
    assert red.busy_s == pytest.approx(busy_ns * 1e-9, rel=0.02)
    assert 0.0 < red.busy_s < red.window_s
    assert sum(v[1] for v in red.ops.values()) == len(ops)
    # nested ops (a loop's body inside its ``while``) count once: self
    # times add up to the busy time
    assert sum(v[0] for v in red.ops.values()) == pytest.approx(
        red.busy_s, rel=1e-6)
    assert all(k.startswith("jit_decode_step/") for k, _ in red.top_ops(5))
    # three decode steps, each one run of the decode executable, with the
    # packed kernel once per linear: 2 layers x 7 linears
    t, n = red.module_time(r"^jit_decode_step$")
    assert n == 3 and 0 < t < red.window_s
    _, k = red.op_time(readers.PACKED_OP_EVENTS["dequant_matmul"])
    assert k == 3 * 2 * 7
    assert red.op_time(r"^%dequant_matmul_pallas\.\d+ = ")[1] == k
    assert red.op_time(r"no such op")[1] == 0
    # two stretches of one run read as one
    two = T.combine([red, red])
    assert two.window_s == pytest.approx(2 * red.window_s)
    assert two.busy_s == pytest.approx(2 * red.busy_s)
    assert two.module_time(r"^jit_decode_step$")[1] == 2 * n
    assert two.op_time(readers.PACKED_OP_EVENTS["dequant_matmul"])[1] == 2 * k
    assert len(two.gaps) == 2 * len(red.gaps)
    assert red.top_ops(3)[0][1] >= red.top_ops(3)[-1][1]
    assert all(s > 0 for s, _ in red.gaps)
    # idle gaps are named by the benchmark thread's own spans
    assert "DevicePut" in dict(red.top_gaps(10))
