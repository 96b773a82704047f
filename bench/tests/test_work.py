"""Operations and bytes against hand counts, the peaks table, and run.py's
refusal to run without a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import configfile, peaks, readers
from bench.harness import RunView
from bench.trace_reduce import Reduced
from bench.work import packed_gemm

ROOT = Path(__file__).resolve().parents[2]
QWEN = json.loads((ROOT / "bench/configs/qwen1.5-0.5b-w2a16.json").read_text())
WORK = configfile.module(QWEN, "work")
V5E = peaks.peaks_for("TPU v5 lite")


def test_packed_gemm_hand_counts():
    # q projection of a decode step: 32 rows, K = N = 1024, w2a16
    assert packed_gemm.ops(32, 1024, 1024) == 2 * 32 * 1024 * 1024
    b = packed_gemm.bytes_moved(32, 1024, 1024, w_bits=2, a_bits=None)
    # 2-bit weights 262144 + f32 scales 4096 + bf16 x 65536 + f32 y 131072
    assert b == 262144 + 4096 + 65536 + 131072
    # w2a2: activations at 2 bits plus one f32 scale per row
    b2 = packed_gemm.bytes_moved(32, 1024, 1024, w_bits=2, a_bits=2)
    assert b2 == 262144 + 4096 + 32 * 1024 // 4 + 4 * 32 + 131072
    grouped = packed_gemm.bytes_moved(1, 1024, 8, w_bits=2, a_bits=None,
                                      group_size=64)
    assert grouped == 1024 * 8 // 4 + 4 * 8 * 16 + 2 * 1024 + 4 * 8


def test_roofline_picks_the_binding_bound():
    # decode rows: bytes bind; 463 KB at 819 GB/s
    t = packed_gemm.roofline_s(32, 1024, 1024, V5E, w_bits=2, a_bits=None)
    assert t == pytest.approx(462848 / 819e9)
    # prefill rows: operations bind, at the bf16 peak for a16 and the int8
    # peak for integer activations
    t16 = packed_gemm.roofline_s(16384, 1024, 2816, V5E, w_bits=2,
                                 a_bits=None)
    assert t16 == pytest.approx(2 * 16384 * 1024 * 2816 / 197e12)
    t2 = packed_gemm.roofline_s(16384, 1024, 2816, V5E, w_bits=2, a_bits=2)
    assert t2 == pytest.approx(2 * 16384 * 1024 * 2816 / 393e12)


def test_model_flops_hand_counts():
    calls = WORK.dense_calls(QWEN)
    assert calls == [(1024, 1024, 24)] * 4 + [(1024, 2816, 24)] * 2 + \
        [(2816, 1024, 24)]
    per_layer = 2 * (4 * 1024 * 1024 + 3 * 1024 * 2816)
    assert WORK.linear_ops(QWEN) == 24 * per_layer
    assert WORK.head_ops(QWEN) == 2 * 1024 * 151936
    assert WORK.decode_ops(QWEN, 100) == \
        24 * per_layer + 4 * 100 * 1024 * 24 + 2 * 1024 * 151936
    # three prompt tokens at positions 5, 6, 7: contexts 5 + 6 + 7 = 18
    assert WORK.prompt_ops(QWEN, 5, 3) == \
        3 * 24 * per_layer + 4 * 18 * 1024 * 24


def test_gqa_untied_hand_counts():
    # codeqwen1.5-7b's widths with grouped-query attention: 32 query heads
    # of 128 over 4 key/value heads, an untied 92416-row head
    gqa = dict(QWEN, hidden_size=4096, intermediate_size=13440,
               num_hidden_layers=32, num_attention_heads=32,
               num_key_value_heads=4, vocab_size=92416,
               tie_word_embeddings=False)
    calls = WORK.dense_calls(gqa)
    assert calls == [(4096, 4096, 32), (4096, 512, 32), (4096, 512, 32),
                     (4096, 4096, 32), (4096, 13440, 32), (4096, 13440, 32),
                     (13440, 4096, 32)]
    per_layer = 2 * (2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 13440)
    assert WORK.linear_ops(gqa) == 32 * per_layer
    # the head is counted once, whether or not it shares the embedding
    assert WORK.head_ops(gqa) == 2 * 4096 * 92416
    # attention counts the 32 query heads: 4 t d_model a layer
    assert WORK.decode_ops(gqa, 100) == \
        32 * per_layer + 4 * 100 * 4096 * 32 + 2 * 4096 * 92416
    assert WORK.prompt_ops(gqa, 5, 3) == \
        3 * 32 * per_layer + 4 * 18 * 4096 * 32


def _view(**kw):
    base = dict(config=QWEN, peaks=V5E, trace=None,
                counters={"decode_steps": 10, "prefill_steps": 1},
                dispatch={"dequant_matmul:pallas": 14}, decode_rows=32,
                prefill_rows=32, decode_module="^jit_decode_step$",
                prefill_module="^jit_prefill_step$",
                decode_contexts=[], prompt_segments=[])
    base.update(kw)
    return RunView(**base)


KERNEL_TEXT = (  # an XLA Ops event of the packed kernel on a v5e
    '%dequant_matmul_pallas.7 = f32[8,256]{1,0:T(8,128)S(1)} custom-call('
    'bf16[4,8,176]{2,1,0:T(8,128)(2,1)S(1)} %multiply_bitcast_fusion.2, '
    'u8[256,176]{1,0:T(8,128)(4,1)S(1)} %bitcast.261, f32[4]{0:T(128)S(1)} '
    '%fusion.129), custom_call_target="tpu_custom_call"')


def test_readers_arithmetic():
    def least(steps):
        return steps * sum(c * packed_gemm.roofline_s(
            32, k, n, V5E, w_bits=2, a_bits=None)
            for k, n, c in WORK.dense_calls(QWEN))

    dec = "jit_decode_step/dequant_matmul_pallas.7"
    pre = "jit_prefill_step/dequant_matmul_pallas.3"
    other = "jit_decode_step/custom-call.3"
    red = Reduced(window_s=1.0, busy_s=0.25, n_devices=1,
                  ops={dec: [4 * least(10), 10 * 24 * 7],
                       pre: [4 * least(1), 24 * 7], other: [0.5, 99]},
                  texts={dec: KERNEL_TEXT, pre: KERNEL_TEXT,
                         other: KERNEL_TEXT.replace("u8[", "s8[")},
                  modules={"jit_decode_step": [0.02, 10]}, gaps=[])
    v = _view(trace=red, decode_contexts=[100] * 4)
    assert readers.packed_gemm_roofline(v) == pytest.approx(25.0)
    assert readers.decode_step_ms(v) == pytest.approx(2.0)
    assert readers.idle_share(v) == pytest.approx(75.0)
    assert readers.mfu(v) == pytest.approx(
        100 * 4 * WORK.decode_ops(QWEN, 100) / 197e12)
    # the kernel is found by its HLO text, whatever its instruction's name
    red.texts[dec] = KERNEL_TEXT.replace("%dequant_matmul_pallas.7",
                                         "%w2a16_matmul.7")
    assert readers.packed_gemm_roofline(v) == pytest.approx(25.0)
    # events the profiler did not keep: the traced calls' mean stands for
    # every call of that executable
    red.ops[dec] = [4 * least(10) * 1650 / 1680, 1650]
    assert readers.packed_gemm_roofline(v) == pytest.approx(25.0)
    # more events than calls, or fewer than half: nothing to read
    for n in (1681, 839):
        red.ops[dec] = [4 * least(10) * n / 1680, n]
        assert readers.packed_gemm_roofline(v) is None
    red.ops[dec] = [4 * least(10), 1680]
    assert readers.packed_gemm_roofline(v) == pytest.approx(25.0)
    # a narrower pattern, as a configuration with a second packed op gives
    assert readers.packed_gemm_roofline(
        v, events=r"u8\[256,176\]") == pytest.approx(25.0)
    assert readers.packed_gemm_roofline(v, events=r"u8\[999,") is None
    # kernel events outside both step executables: nothing to read
    red.ops["no module/dequant_matmul_pallas.9"] = [1.0, 1]
    red.texts["no module/dequant_matmul_pallas.9"] = KERNEL_TEXT
    assert readers.packed_gemm_roofline(v) is None
    # no dispatch of the packed op: nothing to read
    assert readers.packed_gemm_roofline(_view(trace=red, dispatch={})) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in peaks.json"):
        peaks.peaks_for("TPU v9 imaginary")
    assert V5E["bf16_flops"] == 197e12 and V5E["hbm_bytes_per_s"] == 819e9


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        json.loads((ROOT / "BENCHMARK.json").read_text())
                        ["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
