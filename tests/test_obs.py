"""Observability stack (docs/observability.md): metrics registry percentile
math and scoped recording, deterministic lifecycle tracing under a fake
clock, span completeness across preemption-with-requeue, the
zero-jit-entries / token-identity guard for instrumented serving, the dense
shim's forwarded counters, and the trace exports + report renderer."""

import json

import jax
import numpy as np
import pytest

from repro.analysis import report
from repro.configs import get_config, reduce_for_smoke
from repro.models import lm
from repro.obs import FakeClock, Tracer, metrics as obs_metrics
from repro.obs.metrics import MetricsRegistry, m_bucket, percentile, summarize
from repro.serving import ContinuousBatcher, Engine, Request

KEY = jax.random.PRNGKey(0)

_SETUP_CACHE = {}


def _setup(arch="qwen1.5-0.5b"):
    if arch not in _SETUP_CACHE:
        cfg = reduce_for_smoke(get_config(arch))
        params = lm.init_params(KEY, cfg, mode="plain")
        _SETUP_CACHE[arch] = (cfg, params)
    return _SETUP_CACHE[arch]


def _tight_engine(cfg, params, tracer=None):
    """Pool sized so three requests cannot coexist: forces preemption."""
    return Engine(cfg, params, n_slots=2, max_len=64, block_size=8,
                  chunk_size=8, n_blocks=6, max_queue=8, tracer=tracer)


def _submit_three(eng):
    reqs = [Request(uid=uid, prompt=list(range(1, plen + 1)), max_new=mnt,
                    priority=pr)
            for uid, (plen, mnt, pr) in enumerate(
                [(12, 10, 0), (10, 12, 5), (9, 8, 0)])]
    for r in reqs:
        assert eng.submit(r)
    return reqs


# --------------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------------- #

def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    xs = rng.random(37).tolist()
    for q in (0, 10, 25, 50, 75, 95, 99, 100):
        assert percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)
    assert percentile([], 50) is None
    assert percentile([3.0], 99) == 3.0


def test_summarize_empty_and_basic():
    s = summarize([])
    assert s["count"] == 0 and s["p99"] is None
    s = summarize([1, 2, 3, 4])
    assert s["count"] == 4 and s["mean"] == 2.5 and s["p50"] == 2.5


def test_registry_families_and_snapshot():
    reg = MetricsRegistry()
    reg.inc("c", op="x")
    reg.inc("c", 2, op="x")
    reg.set_gauge("g", 7)
    reg.observe("h", 1.0)
    reg.observe("h", 3.0)
    snap = reg.snapshot()
    assert snap["counters"]["c{op=x}"] == 3
    assert snap["gauges"]["g"] == 7
    assert snap["histograms"]["h"]["count"] == 2
    reg.clear("c")
    assert "c{op=x}" not in reg.snapshot()["counters"]
    assert reg.gauge("g") == 7                    # other families untouched


def test_scoped_recording_propagates_and_isolates():
    base = obs_metrics.global_registry().get("t_scoped", op="a")
    with obs_metrics.scoped() as outer:
        obs_metrics.inc("t_scoped", op="a")
        with obs_metrics.scoped() as inner:
            obs_metrics.inc("t_scoped", op="a")
        with obs_metrics.scoped(isolate=True) as iso:
            obs_metrics.inc("t_scoped", op="a")
    # inner scope saw 1, outer saw both non-isolated, isolate saw only its own
    assert inner.get("t_scoped", op="a") == 1
    assert iso.get("t_scoped", op="a") == 1
    assert outer.get("t_scoped", op="a") == 2
    # the isolated record never reached the process-global registry
    assert obs_metrics.global_registry().get("t_scoped", op="a") == base + 2


def test_scoped_existing_registry_routes_records():
    """scoped(registry=...) pushes an existing registry — how the engine
    scopes its jitted calls so trace-time kernel dispatches land in the
    per-engine snapshot (engine.obs)."""
    mine = MetricsRegistry()
    with obs_metrics.scoped() as outer:
        with obs_metrics.scoped(registry=mine) as reg:
            obs_metrics.inc("t_routed", op="a")
        assert reg is mine
    assert mine.get("t_routed", op="a") == 1
    assert outer.get("t_routed", op="a") == 1      # still propagates down


def test_m_bucket_labels():
    assert [m_bucket(m) for m in (None, 1, 4, 8)] == ["na", "1", "4", "8"]
    assert m_bucket(9) == "le16" and m_bucket(16) == "le16"
    assert m_bucket(100) == "le128"


# --------------------------------------------------------------------------- #
# tracer (host-side only: no engine needed)
# --------------------------------------------------------------------------- #

def _drive_fake(tracer):
    tracer.on_submit(0, prompt_len=8)
    tracer.step_begin(0)
    with tracer.phase("admit"):
        tracer.on_admit(0, shared_tokens=0)
    with tracer.phase("prefill"):
        tracer.on_prefill_chunk(0, start=0, rows=8,
                                t0=tracer.now(), t1=tracer.now())
    with tracer.phase("decode"):
        for i in range(3):
            tracer.on_token(0, 7 + i, done=(i == 2))
    tracer.on_finish(0)
    tracer.step_end({"queue_depth": 0, "active_slots": 1})


def test_trace_deterministic_under_fake_clock():
    t1, t2 = Tracer(clock=FakeClock()), Tracer(clock=FakeClock())
    _drive_fake(t1)
    _drive_fake(t2)
    assert t1.chrome_trace() == t2.chrome_trace()
    assert t1.latency_summary() == t2.latency_summary()
    # the fake clock ticks deterministically, so derived stats are exact
    r = t1.requests[0]
    assert r.ttft_s() is not None and len(r.token_times) == 3


def test_preemption_reopens_queued_span_same_trace():
    tr = Tracer(clock=FakeClock())
    tr.on_submit(0, prompt_len=8)
    tr.on_admit(0)
    tr.on_token(0, 5, done=False)
    tr.on_preempt(0)                       # evicted: back to the queue
    tr.on_admit(0)                         # re-admitted later
    tr.on_token(0, 6, done=True)
    tr.on_finish(0)
    assert len(tr.requests) == 1           # ONE trace across the requeue
    r = tr.requests[0]
    assert len(r.preempt_times) == 1 and r.finished is not None
    names = [s.name for s in r.spans]
    assert names.count("queued") == 2, names   # original + post-preempt
    assert all(s.t1 is not None for s in r.spans)


def test_rejected_request_traced():
    tr = Tracer(clock=FakeClock())
    tr.on_reject(1, prompt_len=500)
    assert tr.requests[1].rejected
    assert tr.latency_summary()["ttft_s"]["count"] == 0


# --------------------------------------------------------------------------- #
# engine integration: guards the instrumentation cannot perturb serving
# --------------------------------------------------------------------------- #

def test_tracing_zero_new_jit_entries_and_identical_tokens():
    cfg, params = _setup()
    traced = _tight_engine(cfg, params, tracer=Tracer(clock=FakeClock()))
    plain = _tight_engine(cfg, params)
    r1 = _submit_three(traced)
    r2 = _submit_three(plain)
    m1, m2 = traced.run(), plain.run()
    assert [r.out for r in r1] == [r.out for r in r2]
    assert traced.n_compiles() == plain.n_compiles()
    assert m1["preemptions"] >= 1          # the workload actually preempts
    tr = traced.tracer
    pre = [r for r in tr.requests.values() if r.preempt_times]
    assert pre, "preemption not traced"
    assert len(tr.requests) == 3
    assert all(r.finished is not None for r in tr.requests.values())
    # phase timeline covered every engine step and sampled gauges
    ph = tr.phase_summary()
    assert ph["n_steps"] == m1["engine_steps"]
    assert tr.steps[0]["gauges"]["free_blocks"] is not None
    # registry snapshot carries the engine counters + compile tracking
    snap = m1["metrics"]
    assert snap["counters"]["engine_preemptions"] == m1["preemptions"]
    assert any(k.startswith("jit_compiles_total") for k in snap["counters"])


def _host_events(logdir) -> set:
    """Names of the host spans in the one profiler trace under ``logdir``."""
    from jax.profiler import ProfileData

    (path,) = logdir.glob("**/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return {ev.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_engine_steps_and_phases_on_the_profiler_trace(tmp_path):
    """With a tracer attached, each engine step and phase is a host span
    on the profiler's clock; without one the engine records none."""
    cfg, params = _setup()
    traced = _tight_engine(cfg, params, tracer=Tracer())
    plain = _tight_engine(cfg, params)
    for eng in (traced, plain):
        _submit_three(eng)
        eng.step()                         # compiles outside the traces
    with jax.profiler.trace(str(tmp_path / "traced")):
        for _ in range(3):
            traced.step()
    with jax.profiler.trace(str(tmp_path / "plain")):
        for _ in range(3):
            plain.step()
    assert {"engine.step", "engine.admit", "engine.prefill",
            "engine.decode"} <= _host_events(tmp_path / "traced")
    assert not {n for n in _host_events(tmp_path / "plain")
                if n.startswith("engine.")}


def test_serve_fixed_batch_steps_on_the_profiler_trace(tmp_path,
                                                       monkeypatch):
    """serve.py's fixed-batch loop names each step on the profiler's trace:
    the prefill, then one span per decode step."""
    import sys

    from repro.launch import compile_cache, serve

    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "qwen1.5-0.5b", "--smoke", "--batch", "2",
        "--prompt-len", "8", "--gen", "3"])
    with jax.profiler.trace(str(tmp_path)):
        assert serve.main() == 0
    assert {"prefill", "decode"} <= _host_events(tmp_path)


def test_engine_counter_properties_assignable():
    """benchmarks/serving.py zeroes counters by assignment after warmup;
    the registry-backed properties must keep that working."""
    cfg, params = _setup()
    eng = _tight_engine(cfg, params)
    _submit_three(eng)
    eng.run()
    assert eng.steps > 0 and eng.prefill_chunks > 0
    eng.steps = eng.decode_steps = eng.prefill_chunks = 0
    eng.prefill_tokens_computed = eng.prefill_tokens_shared = 0
    assert eng.steps == 0 and eng.prefill_tokens_computed == 0
    assert eng.obs.get("engine_steps") == 0


def test_dense_shim_forwards_engine_counters():
    """ISSUE 7 satellite: the ContinuousBatcher path must report real
    prefill/preemption counters (they were nulls in BENCH_serving.json)."""
    cfg, params = _setup()
    cb = ContinuousBatcher(cfg, params, n_slots=2, max_len=32)
    r = Request(uid=0, prompt=[1, 2, 3], max_new=4)
    cb.submit(r)
    m = cb.run()
    assert r.done
    assert m["prefill_tokens_computed"] == 3
    assert m["preemptions"] == 0 and m["prefill_tokens_shared"] == 0
    assert "steps" in m and "slot_utilization" in m     # legacy keys stay


# --------------------------------------------------------------------------- #
# exports + report renderer
# --------------------------------------------------------------------------- #

def test_chrome_trace_schema(tmp_path):
    tr = Tracer(clock=FakeClock())
    _drive_fake(tr)
    p = str(tmp_path / "trace.json")
    tr.export(p)
    doc = json.load(open(p))
    ev = doc["traceEvents"]
    assert all("ph" in e and "pid" in e for e in ev)
    assert any(e["ph"] == "X" and e["pid"] == 1 for e in ev)   # request spans
    assert any(e["ph"] == "X" and e["pid"] == 0 for e in ev)   # engine phases
    assert any(e["ph"] == "C" for e in ev)                     # gauge counters
    rp = doc["repro"]
    assert rp["requests"][0]["n_tokens"] == 3
    assert rp["latency"]["ttft_s"]["count"] == 1


def test_report_renders_both_trace_formats(tmp_path):
    tr = Tracer(clock=FakeClock())
    _drive_fake(tr)
    pj = str(tmp_path / "t.json")
    pl = str(tmp_path / "t.jsonl")
    tr.export(pj)
    tr.export(pl)
    for p in (pj, pl):
        txt = report.trace_report(report.load_trace(p))
        assert "Latency percentiles" in txt and "Step phases" in txt
        assert "| ttft |" in txt and "| decode |" in txt
    # same underlying trace -> same normalized report
    assert (report.trace_report(report.load_trace(pj))
            == report.trace_report(report.load_trace(pl)))
