"""Device time by layer name (``bench/scope_time.py``): on a hand-built
reduced trace, and on a small trace recorded on a TPU v5e
(``data/v5e_scoped.xplane.pb``, made by ``record_trace.py``: three decode
steps of a two-layer, 256-wide qwen1.5-shaped model packed at w2a16, the
program's layer names on its operations)."""

from pathlib import Path

import pytest

from bench import readers, scope_time as S, trace_reduce as T
from bench.harness import RunView

TRACE = Path(__file__).resolve().parent / "data" / "v5e_scoped.xplane.pb"
DECODE = r"^jit_decode_step$"


def _op(name, scope=None, extra=""):
    attrs = [a for a in (extra, f'scope="{scope}"' if scope else "") if a]
    text = f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)"
    return text + (f", frontend_attributes={{{','.join(attrs)}}}"
                   if attrs else "")


def _reduced(ops: dict) -> T.Reduced:
    """A reduced trace of one decode executable from {key: (self seconds,
    count, HLO text)}."""
    return T.Reduced(
        window_s=1.0, busy_s=sum(v[0] for v in ops.values()), n_devices=1,
        ops={k: [v[0], v[1]] for k, v in ops.items()},
        texts={k: v[2] for k, v in ops.items()},
        modules={"jit_decode_step": [0.5, 2], "jit_prefill_step": [0.3, 1]},
        gaps=[])


def _view(trace, decode_steps=2, prefill_steps=1) -> RunView:
    return RunView(config={}, peaks={}, trace=trace,
                   counters={"decode_steps": decode_steps,
                             "prefill_steps": prefill_steps},
                   dispatch={}, decode_rows=8, prefill_rows=256,
                   decode_module=DECODE, prefill_module=r"^jit_prefill_step$",
                   decode_contexts=[], prompt_segments=[])


# A decode executable whose scan (a ``while`` named ``layers``) holds the
# layers' ops: the while's own time is its self time, what its body's ops
# do not cover; a Pallas call keeps its kernel_metadata beside the name.
OPS = {
    "jit_decode_step/while.3": (0.010, 2, _op("while.3", "layers")),
    "jit_decode_step/fusion.1": (0.200, 2, _op("fusion.1", "attn/core")),
    "jit_decode_step/fusion.2": (0.050, 2, _op("fusion.2", "attn/core")),
    "jit_decode_step/fusion.3": (0.040, 2, _op("fusion.3", "attn/kv_write")),
    "jit_decode_step/dequant_matmul_pallas.4": (
        0.060, 2, _op("dequant_matmul_pallas.4", "mlp/up",
                      "kernel_metadata={}")),
    "jit_decode_step/copy.5": (0.100, 2, _op("copy.5")),
    "jit_decode_step/fusion.6": (0.002, 2, _op("fusion.6", "lm_head")),
    "jit_prefill_step/fusion.1": (0.250, 1, _op("fusion.1", "attn/core")),
    "jit_prefill_step/copy.2": (0.020, 1, _op("copy.2")),
}


def test_attribution_by_scope_and_self_time():
    run = _view(_reduced(OPS))
    assert S.decode_ms(run, "attn/core") == pytest.approx(1e3 * 0.25 / 2)
    assert S.decode_ms(run, "attn/kv_write") == pytest.approx(20.0)
    assert S.decode_ms(run, "mlp/up") == pytest.approx(30.0)
    assert S.decode_ms(run, "lm_head") == pytest.approx(1.0)
    # the loop counts only its self time, not its body's
    assert S.decode_ms(run, "layers") == pytest.approx(5.0)
    assert S.decode_ms(run, "norm") == 0.0
    assert S.prefill_ms(run, "attn/core") == pytest.approx(250.0)
    assert S.scope_of(OPS["jit_decode_step/dequant_matmul_pallas.4"][2]) == \
        "mlp/up"
    assert S.scope_of("%copy.5 = f32[8]{0} copy(f32[8]{0} %p)") == S.UNSCOPED


def test_unscoped_excludes_named_ops():
    run = _view(_reduced(OPS))
    assert S.decode_ms(run, S.UNSCOPED) == pytest.approx(50.0)
    assert S.prefill_ms(run, S.UNSCOPED) == pytest.approx(20.0)


def test_the_split_partitions_the_executable():
    red = _reduced(OPS)
    for module, prefix in ((DECODE, "jit_decode_step/"),
                           (r"^jit_prefill_step$", "jit_prefill_step/")):
        parts = S.split(red, module)
        total = sum(v[0] for k, v in OPS.items() if k.startswith(prefix))
        assert sum(parts.values()) == pytest.approx(total)
    assert set(S.split(red, DECODE)) == {
        "layers", "attn/core", "attn/kv_write", "mlp/up", "lm_head",
        S.UNSCOPED}


def test_none_without_names_or_trace():
    plain = {k: (s, n, _op(k.split("/")[1])) for k, (s, n, _) in OPS.items()}
    run = _view(_reduced(plain))
    assert S.split(run.trace, DECODE) is None
    assert S.decode_ms(run, "attn/core") is None
    assert S.decode_ms(run, S.UNSCOPED) is None
    assert S.decode_ms(_view(None), "attn/core") is None
    assert S.prefill_ms(_view(_reduced(OPS), prefill_steps=0),
                        "attn/core") is None
    # another attribute alone is no name
    pallas = {"jit_decode_step/p.1": (0.1, 1, _op("p.1", None,
                                                  "kernel_metadata={}"))}
    assert S.split(_reduced(pallas), DECODE) is None


def test_recorded_v5e_trace():
    red = T.reduce(str(TRACE))
    run = _view(red, decode_steps=3, prefill_steps=0)
    parts = S.split(red, DECODE)
    assert {"attn/core", "attn/kv_write", "attn/qkv", "attn/out", "mlp/up",
            "mlp/down", "norm", "lm_head", "embed"} <= set(parts)
    # the layers and the nameless ops partition the executable's op time
    ops_s = sum(v[0] for k, v in red.ops.items()
                if k.startswith("jit_decode_step/"))
    assert sum(parts.values()) == pytest.approx(ops_s)
    assert S.decode_ms(run, "attn/core") > 0
    # the Pallas kernels keep their own attribute beside the layer name,
    # and the packed-op match still finds every call: 3 steps x 2 layers
    # x 7 linears
    packed = red.op_hits(readers.PACKED_OP_EVENTS["dequant_matmul"])
    assert sum(v[1] for v in packed.values()) == 3 * 2 * 7
    for key in packed:
        assert "kernel_metadata={}" in red.texts[key]
        assert S.scope_of(red.texts[key]) in {"attn/qkv", "attn/out",
                                              "mlp/up", "mlp/down"}
