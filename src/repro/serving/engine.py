"""Streaming continuous-batching engine over the paged KV-cache pool.

The engine owns (1) a paged cache (serving/cache.py): per-layer block pools
plus a host-side BlockPool allocator, (2) an optional prefix-sharing radix
cache (serving/radix.py) indexing already-filled prompt blocks, and (3) a
fixed set of jit'd fixed-shape step functions, so steady-state serving never
recompiles:

  _decode          batched one-token step over all n_slots (active or not);
                   inactive rows write to the null block and are masked out.
  _prefill_chunk   single-request chunk of `chunk_size` prompt tokens written
                   straight into the request's pool blocks. Long prompts are
                   admitted chunk by chunk, interleaved with decode steps, so
                   they never head-of-line-block running requests.
  _prefill_batched (prefill_batch > 1) the same chunk math over a fixed
                   batch of `prefill_batch` requests, padded with inert rows
                   whose tables point at the null block — short-prompt
                   bursts admit in one forward instead of prefill_batch.
  _sample          the jit'd per-request sampler stack (serving/sampler.py):
                   temperature -> top-k -> top-p -> seeded categorical.
                   Greedy rows (the default) collapse to exact argmax, so
                   default decoding is unchanged; seeded sampled decode is
                   bit-reproducible across runs and batch compositions.
  _draft / _verify / _draft_prefill / _spec_accept
                   (spec_draft_params set) SELF-SPECULATIVE decoding: a
                   low-bit drafter (e.g. the same weights quantize_tree'd
                   to w2a2) proposes spec_k tokens per round against its
                   own paged KV — a second cache tree addressed by the same
                   BlockPool — and the target verifies all of them in one
                   fixed-shape (n_slots, spec_k+1) forward. Lossless
                   rejection sampling (serving/spec.py) emits 1..spec_k+1
                   tokens per round with EXACTLY the target-only output
                   distribution; greedy spec decode is bit-identical to
                   non-spec greedy. Drafter KV is best-effort: it is the
                   first thing reclaimed under pool pressure, and a slot
                   whose drafter lags just decodes un-speculated through
                   the same two traces.

Scheduling policy per `step()`: admit from the bounded queue while free
slots AND first-chunk blocks exist -> run one prefill chunk (round-robin
over prefilling slots; up to prefill_batch of them fused into one batched
chunk) -> run one batched decode step.

Prefix sharing (prefix_cache=True): admission looks the effective prompt up
in the radix cache; the longest block-aligned cached prefix is attached by
refcount bump and prefill starts after it (`prefill_done = matched`). After
every chunk the request's fully-filled prompt blocks are inserted into the
tree, so concurrent and later requests share them — a full-prompt hit skips
prefill entirely. When the pool runs low, unreferenced cached blocks are
LRU-evicted before any live request is preempted (see serving/radix.py for
the ownership protocol). Sharing requires chunked prefill and an arch
without per-slot recurrent state; it is silently disabled otherwise (check
`engine.radix is not None`).

Preemption: when a request needs a block and the pool is exhausted, the
lowest-priority occupied slot (ties: latest admitted) is evicted — its
blocks are freed and it is requeued at the front with its generated tokens
folded into the prompt (recompute-style preemption), so it resumes exactly
where it left off after re-prefill. Blocks the radix tree indexes survive
the preemption (the tree holds its own reference) and typically let the
re-prefill skip the part that was already done.

Determinism contract (tested): with a bf16 pool, greedy decode through the
engine is bit-identical to decoding the request alone, because slot rows
are disjoint (batch-independent math), masked cache positions contribute
exact zeros, and the decode math on the gathered block view is the same
masked softmax as the dense path. Prefix sharing and batched prefill keep
this bit-identity: a matched block holds exactly the bytes re-prefilling
the same tokens would write, and batched prefill rows are batch-independent
(pad rows write only the null block). Quantized pools (int8/int4) quantize
K/V at write time, so chunked prefill sees dequantized history where
whole-prompt prefill attends raw bf16 — serving stays deterministic
run-to-run but is not bit-identical to the unquantized isolated decode.
Recurrent archs likewise may drift ulps (the associative scan's split
points move with the chunking).

`prefill="whole"` replays the legacy dense batcher's admission (one
whole-prompt forward per request, recompiling per prompt length); the
ContinuousBatcher shim uses it to stay bit-identical to the pre-paged
scheduler. `prefill="chunked"` is the default and the fast path.

Observability (docs/observability.md): every counter lives in a PER-ENGINE
metrics registry (``engine.obs``, snapshot in ``metrics()["metrics"]``),
and an optional ``tracer`` records request lifecycle spans (queued ->
prefill -> decode, preemption events) plus a per-step phase timeline with
pool/queue gauges. All instrumentation runs in the host scheduling loop,
strictly outside the jit'd step functions — tracing adds zero jit cache
entries and cannot perturb the token stream (guard-tested).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import zlib
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import sharding as Sh
from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.obs.metrics import MetricsRegistry
from . import cache as C
from . import sampler as S
from . import spec as SP
from .radix import RadixCache


@dataclasses.dataclass
class Request:
    """One generation request.

    Fields set by the caller:
      uid       opaque id (echoed in logs/metrics, not interpreted)
      prompt    (P,) int32 token ids; P == 0 is legal (decode from BOS-less
                empty context)
      max_new   generation budget; decoding also stops at eos_id or when the
                context hits the engine's max_len - 1
      eos_id    stop token (None: run to max_new)
      priority  preemption order under pool exhaustion — LOWER priority is
                evicted first; ties evict the latest-admitted slot
      on_token  streaming callback, called as on_token(token: int,
                done: bool) from inside `step()` in generation order
      temperature / top_p
                per-request sampler overrides (None: the engine's
                SamplerConfig defaults apply; see serving/sampler.py).
                temperature 0 is greedy argmax. For the seeded sampler the
                uid doubles as the per-request PRNG stream id, so two
                requests with the same (seed, uid) prompt-independently
                draw identical token streams

    Fields filled by the engine:
      out         generated token ids (ints), streamed in order
      done        True once the request completed (not set for rejected)
      rejected    True if admission control refused the request
      n_preempted times this request was evicted and re-queued
    """
    uid: int
    prompt: jax.Array            # (P,) int32 (P may be 0)
    max_new: int = 16
    eos_id: Optional[int] = None
    priority: int = 0            # lower priority is preempted first
    on_token: Optional[Callable[[int, bool], None]] = None   # streaming
    temperature: Optional[float] = None   # None: engine sampler default
    top_p: Optional[float] = None         # None: engine sampler default
    # filled by the engine
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    rejected: bool = False
    n_preempted: int = 0


_FREE, _PREFILL, _DECODE = 0, 1, 2


def _counter(metric: str, doc: str):
    """Engine counter attribute backed by the per-engine metrics registry
    (``engine.obs``): reads/writes hit one counter, so ``metrics()``
    snapshots and benchmark-window resets (``eng.steps = 0``) stay in
    sync with the registry by construction."""
    def _get(self) -> int:
        return int(self.obs.get(metric))

    def _set(self, v: int) -> None:
        self.obs.set_counter(metric, v)

    return property(_get, _set, doc=doc)


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    state: int = _FREE
    prompt: Optional[np.ndarray] = None   # effective prompt (+ regenerated)
    prefill_done: int = 0                 # prompt rows already in the cache
    pos: int = 0                          # next decode row (== ctx length)
    next_input: int = 0
    blocks: list = dataclasses.field(default_factory=list)
    admit_seq: int = 0
    # speculative decoding: drafter-KV blocks (same pool id space as
    # `blocks` but written by the DRAFT cache tree) and how many drafter
    # rows mirror the target's fed-token stream (draft_done == pos: synced)
    draft_blocks: list = dataclasses.field(default_factory=list)
    draft_done: int = 0
    # ring-paged local layers (engine ring=True): fixed per-slot rings of
    # ring_len blocks from the DEDICATED ring pool (own id space); target
    # and drafter rings live for the whole slot occupancy
    ring_blocks: list = dataclasses.field(default_factory=list)
    draft_ring_blocks: list = dataclasses.field(default_factory=list)
    # radix insert resume hint: deepest indexed node + blocks indexed so
    # far (valid while this slot lives — see RadixCache.insert)
    radix_node: object = None
    radix_done: int = 0


class Engine:
    """Paged continuous-batching engine (see module docstring).

    Constructor arguments:
      cfg, params    model config + parameter tree (bf16 or quantize_tree'd)
      n_slots        decode batch width (fixed shape of the decode step)
      max_len        max context rows per request; multiple of block_size
      block_size     tokens per paged KV block
      n_blocks       physical pool size incl. the null block (default: every
                     slot can hold max_len rows, so preemption never fires)
      chunk_size     prefill chunk length (multiple of block_size, divides
                     max_len; default ~2 blocks)
      max_queue      bounded admission queue; submit() beyond it rejects
      prefill        "chunked" (default) | "whole" (legacy admission)
      prefill_batch  requests fused per prefill chunk step (fixed shape,
                     padded; forced to 1 for recurrent archs / whole mode)
      prefix_cache   enable the prefix-sharing radix cache (chunked,
                     attention-only archs; silently disabled otherwise)
      sample         OPTIONAL legacy host-side hook: logits (n_slots, V) f32
                     -> next token ids (n_slots,). None (default) routes
                     every decode draw through the jit'd sampler stack
                     (serving/sampler.py) configured by ``sampler`` — the
                     default SamplerConfig is greedy and bit-identical to
                     the historical argmax lambda. Incompatible with
                     speculative decoding (the hook sees only logits, not
                     the warped distributions rejection sampling needs)
      sampler        SamplerConfig (temperature/top_k/top_p/seed) — engine
                     defaults; Request.temperature / Request.top_p override
                     per request. Seeded draws are bit-reproducible across
                     runs and scheduling changes (keys derive from
                     (seed, uid, sample index) only)
      spec_draft_params
                     optional second parameter tree (same cfg — typically a
                     low-bit quantize_tree of the same weights, e.g. w2a2)
                     enabling SELF-SPECULATIVE decoding: the drafter
                     proposes spec_k tokens per round against its own paged
                     KV (a second cache tree sharing this engine's
                     BlockPool id space) and the target verifies all of
                     them in ONE fixed-shape (n_slots, spec_k+1) forward.
                     Lossless rejection sampling (serving/spec.py) keeps
                     the output distribution exactly the target's — greedy
                     spec decode is bit-identical to non-spec greedy.
                     Requires chunked prefill, an attention-only arch, and
                     sample=None
      spec_draft_cfg config the drafter params were built against (same
                     architecture; typically dataclasses.replace(cfg,
                     quant=get_plan("w2a2")) so forward dispatches the LUT
                     kernels). None: the target cfg
      spec_k         draft tokens per speculative round (>= 1)
      tracer         optional repro.obs.Tracer: per-request lifecycle spans
                     + a per-step phase timeline, recorded from the host
                     scheduling loop only (never inside the jit'd steps; no
                     new jit entries, token stream unchanged). None
                     (default): every hook is one `is None` check.
      mesh           optional jax Mesh with a "model" axis: the engine runs
                     TENSOR-PARALLEL over it. Parameters are placed sharded
                     (dist.sharding.param_specs — packed codes/scales along
                     N for column-parallel layers, along K for row-parallel
                     ones), the paged KV pool shards head-wise
                     (cache.paged_cache_specs), and every jit'd step traces
                     under use_rules + use_tp so activations follow the
                     'serve_tp' preset and planned kernels run shard_map'd
                     (kernels/ops). None (default): single-device, byte-for-
                     byte the pre-TP engine.
      rules          preset name (or rules dict) used with ``mesh``
      ring           ring-page the LOCAL (sliding-window) attention layers:
                     each slot's local-layer KV lives in a fixed per-slot
                     ring of ceil((window + span - 1)/block_size) blocks
                     from a DEDICATED ring pool (span = the largest multi-
                     row advance: prefill chunk / spec verify width), so
                     local-layer memory per request is O(window) — flat in
                     context length — instead of O(max_len). Requires local
                     layers with a window; incompatible with prefix_cache
                     (a radix hit skips prefill, leaving ring rows
                     unwritten). Token-identical to the non-ring engine on
                     gemma3-style archs (regression-tested), but not
                     bitwise on logits (the ring rotates the softmax
                     summation order), hence opt-in.
      kv_splits      flash-decoding split count for the decode-shaped steps
                     (S == 1): the paged KV walk is partitioned into this
                     many chunks with an exact log-sum-exp merge
                     (kernels/paged_attention.py). "auto" (default) picks
                     max(1, min(16, max_len // 4096)) — engines with
                     max_len <= 4096 resolve to 1 and keep the single-pass
                     path byte-for-byte. Static per engine: no new jit
                     entries between steps.

    All device state lives in `self.caches` (the paged tree) and flows
    through the jit'd step functions with donated buffers; everything else
    is host-side Python bookkeeping. Host-side scheduling (admission,
    preemption, radix sharing, block accounting) is mesh-agnostic: a block
    id addresses the same (head-sharded) physical block on every device.
    """

    def __init__(self, cfg, params, *, n_slots: int, max_len: int,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 chunk_size: Optional[int] = None, max_queue: int = 64,
                 prefill: str = "chunked", prefill_batch: int = 1,
                 prefix_cache: bool = False,
                 sample: Optional[Callable] = None,
                 sampler: Optional[S.SamplerConfig] = None,
                 spec_draft_params=None, spec_draft_cfg=None, spec_k: int = 4,
                 tracer=None, mesh=None, rules="serve_tp",
                 ring: bool = False, kv_splits="auto"):
        if cfg.is_encdec:
            raise NotImplementedError("engine: encoder-decoder serving")
        if cfg.mrope_sections or cfg.n_vision_tokens:
            raise NotImplementedError("engine: M-RoPE / vision frontends")
        if cfg.pos_embed == "learned":
            raise NotImplementedError("engine: learned positional embeddings")
        assert max_len % block_size == 0, (max_len, block_size)
        if chunk_size is None:
            chunk_size = min(2 * block_size, max_len)
            while max_len % chunk_size:
                chunk_size -= block_size
        assert chunk_size % block_size == 0 and max_len % chunk_size == 0
        assert prefill in ("chunked", "whole")

        self.mesh = mesh
        self.rules = Sh.PRESETS[rules] if isinstance(rules, str) else rules
        if mesh is not None:
            assert "model" in mesh.shape, mesh
            # place parameters against the mesh ONCE (offline): per-device
            # weight bytes drop to ~1/N for every dividing dim
            params = jax.device_put(
                params, Sh.param_specs(params, mesh, self.rules))

        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.block_size = block_size
        self.chunk_size = chunk_size
        self.max_queue = max_queue
        self.prefill_mode = prefill
        self.nb_max = max_len // block_size
        # spec decoding doubles KV demand (target + drafter rows): default
        # the pool so every slot can hold max_len rows in BOTH trees
        self.n_blocks = n_blocks if n_blocks is not None \
            else (2 if spec_draft_params is not None else 1) \
            * n_slots * self.nb_max + 1
        self.sample = sample            # legacy hook; None = jit'd stack
        self.sampler = sampler if sampler is not None else S.SamplerConfig()
        self.spec = spec_draft_params is not None
        self.spec_k = int(spec_k)
        # verify/draft block tables are widened past nb_max so the up-to-k
        # overflow rows near the context limit scatter into the null block
        # instead of wrapping onto a real one (emitted tokens are capped by
        # the context room, so null-block garbage is never attended)
        self.nb_spec = self.nb_max + (
            -(-(self.spec_k + 1) // block_size) if self.spec else 0)

        # ring-paged local layers (opt-in): each slot's local-layer KV lives
        # in a fixed ring of ring_len blocks (absolute row t at ring row
        # t mod R), so local-layer memory per request is O(window) — flat in
        # context length — instead of O(max_len). The ring carries a cushion
        # past the window because a multi-row forward (prefill chunk / spec
        # verify) attends BEFORE it scatters and may plant up to span-1
        # pad/rejected rows past the kept position: R >= window + span - 1
        # keeps every row a later query can claim alive, and pushes planted
        # garbage a full R below any position the recency mask would accept.
        # Whole-mode prefill scatters host-side (exactly the last min(P, R)
        # real rows), so span collapses to 1 there: ceil(window/block_size)
        # blocks per slot, as small as the window allows.
        self.ring_len = 0
        self.n_ring_blocks = 0
        if ring:
            if not any(t == "local" for t in cfg.pattern) or not cfg.window:
                raise ValueError(
                    "ring=True requires local attention layers with a "
                    "sliding window (cfg.pattern / cfg.window)")
            if prefix_cache:
                raise ValueError(
                    "ring=True is incompatible with prefix_cache: a radix "
                    "hit skips prefill for the matched rows, which would "
                    "leave their ring slots unwritten")
            span = 1
            if prefill == "chunked":
                span = max(span, chunk_size)
            if spec_draft_params is not None:
                span = max(span, self.spec_k + 1)
            self.ring_len = -(-(cfg.window + span - 1) // block_size)
            self.n_ring_blocks = (
                (2 if spec_draft_params is not None else 1)
                * n_slots * self.ring_len + 1)

        # flash-decoding split-KV (kernels/paged_attention.py): static split
        # count threaded into the decode-shaped forwards only (S == 1; the
        # merge is exact, see merge_splitkv_partials). "auto" keys off the
        # max KV length per slot — short-context engines resolve to 1 and
        # keep the single-pass path byte-for-byte; long-context ones walk
        # the block table in ~4k-row chunks so the per-step working set
        # stays one chunk instead of the full dequantized view.
        if kv_splits == "auto":
            self.kv_splits = max(1, min(16, max_len // 4096))
        else:
            self.kv_splits = int(kv_splits)
            if self.kv_splits < 1:
                raise ValueError(f"kv_splits must be >= 1: {kv_splits!r}")

        self.caches = C.init_paged_cache(cfg, n_slots, self.n_blocks,
                                         block_size,
                                         ring_blocks=self.n_ring_blocks
                                         or None)
        self._cache_specs = None
        if mesh is not None:
            self._cache_specs = C.paged_cache_specs(self.caches, mesh,
                                                    self.rules)
            self.caches = jax.device_put(self.caches, self._cache_specs)
        self.pool = C.BlockPool(self.n_blocks)
        # the ring pool is DEDICATED (own id space, own null block): rings
        # are allocated whole at admission and freed at finish/preempt, and
        # the pool is sized so every slot (target + drafter) always fits —
        # ring allocation can never fail and never contends with the main
        # pool's preemption/eviction machinery
        self.ring_pool = C.BlockPool(self.n_ring_blocks) \
            if self.ring_len else None
        self._has_state = C.has_per_slot_state(self.caches)
        self.draft_params = None
        self.draft_caches = None
        self._draft_cache_specs = None
        if self.spec:
            if self._has_state:
                raise NotImplementedError(
                    "spec decoding: recurrent per-slot state (the drafter "
                    "cannot rewind a scan state past rejected tokens)")
            if prefill != "chunked":
                raise ValueError("spec decoding requires chunked prefill")
            if sample is not None:
                raise ValueError(
                    "spec decoding requires the built-in sampler stack "
                    "(a sample= hook sees only logits, not the warped "
                    "distributions rejection sampling needs)")
            assert self.spec_k >= 1, spec_k
            dparams = spec_draft_params
            if mesh is not None:
                dparams = jax.device_put(
                    dparams, Sh.param_specs(dparams, mesh, self.rules))
            self.draft_params = dparams
            self.draft_cfg = spec_draft_cfg if spec_draft_cfg is not None \
                else cfg
            # the drafter's paged KV: a SECOND cache tree addressed by the
            # SAME BlockPool ids, so one allocator arbitrates target vs
            # drafter residency (drafter blocks are reclaimed first)
            self.draft_caches = C.init_paged_cache(
                self.draft_cfg, n_slots, self.n_blocks, block_size,
                ring_blocks=self.n_ring_blocks or None)
            if mesh is not None:
                self._draft_cache_specs = C.paged_cache_specs(
                    self.draft_caches, mesh, self.rules)
                self.draft_caches = jax.device_put(self.draft_caches,
                                                   self._draft_cache_specs)
        # batched prefill pads with inert rows — recurrent state must see
        # exactly the prompt tokens, so stateful archs stay one-per-chunk
        self.prefill_batch = 1 if (self._has_state or prefill == "whole") \
            else max(1, min(prefill_batch, n_slots))
        # prefix sharing aliases attention blocks between requests; per-slot
        # recurrent state has no block boundary to share at, and whole-mode
        # prefill recomputes from scratch (it cannot consume cached blocks)
        self.radix = RadixCache(self.pool, block_size) \
            if (prefix_cache and prefill == "chunked"
                and not self._has_state) else None
        self.slots = [_Slot() for _ in range(n_slots)]
        self.queue: deque[Request] = deque()

        # parameters are an ARGUMENT of every step function (argument 0,
        # caches argument 1): closed over, they would be baked into each
        # executable as constants (GBs at published widths, minutes to
        # compile)
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
        self._prefill_chunk = jax.jit(self._prefill_fn, donate_argnums=(1,))
        self._prefill_batched = jax.jit(self._prefill_batched_fn,
                                        donate_argnums=(1,))
        self._prefill_whole = jax.jit(self._prefill_whole_fn,
                                      donate_argnums=(1,))
        # partial() gives each engine its own jit wrapper over the
        # module-level reset_slot: jitting C.reset_slot directly shares one
        # pjit cache across every engine in the process, so n_compiles()
        # would count traces other engines compiled
        self._reset = jax.jit(functools.partial(C.reset_slot),
                              donate_argnums=(0,))
        self._sample = jax.jit(self._sample_fn)
        if self.spec:
            self._draft = jax.jit(self._draft_fn, donate_argnums=(1,))
            self._verify = jax.jit(self._verify_fn, donate_argnums=(1,))
            self._draft_prefill = jax.jit(self._draft_prefill_fn,
                                          donate_argnums=(1,))
            self._spec_accept = jax.jit(self._spec_accept_fn)

        # observability: a per-engine metrics registry backs every counter
        # attribute below (no process-global state — two engines never see
        # each other's counts), plus an optional lifecycle/timeline tracer
        self.obs = MetricsRegistry()
        self.tracer = tracer
        self._peaks: dict[str, int] = {}
        self._admit_counter = 0
        self._pf_rr = 0
        self._dpf_rr = 0

    # counters (engine.obs-backed; see _counter)
    steps = _counter("engine_steps",
                     "engine steps (admit+prefill+decode)")
    decode_steps = _counter("engine_decode_steps", "batched decode steps")
    prefill_chunks = _counter(
        "engine_prefill_chunks",
        "prefill chunk launches (a batched launch is 1)")
    busy_slot_steps = _counter("engine_busy_slot_steps",
                               "sum over decode steps of active slots")
    preemptions = _counter("engine_preemptions", "slots evicted + requeued")
    rejections = _counter("engine_rejections", "admissions refused")
    prefill_tokens_computed = _counter(
        "engine_prefill_tokens_computed",
        "real prompt rows run through prefill")
    prefill_tokens_shared = _counter(
        "engine_prefill_tokens_shared",
        "prompt rows attached from the radix cache")
    spec_rounds = _counter("spec_rounds_total",
                           "speculative draft+verify rounds")
    spec_draft_tokens = _counter("spec_draft_tokens_total",
                                 "draft tokens proposed to the verifier")
    spec_accepted = _counter("spec_accepted_total",
                             "draft tokens accepted AND emitted")
    spec_emitted = _counter("spec_emitted_total",
                            "tokens emitted by speculative rounds")
    spec_draft_evictions = _counter(
        "spec_draft_evictions_total",
        "drafter-KV evictions under pool pressure")

    def attach_tracer(self, tracer) -> None:
        """Attach (or swap) the lifecycle tracer after construction — e.g.
        after an untraced warmup, so the trace covers only the measured
        window."""
        self.tracer = tracer

    _NULL_CTX = contextlib.nullcontext()     # stateless, safe to share

    def _phase(self, name: str):
        """Tracer phase context for the host scheduling loop (no-op without
        a tracer)."""
        tr = self.tracer
        return tr.phase(name) if tr is not None else Engine._NULL_CTX

    def _run_jit(self, name: str, fn, *args):
        """Call a jit'd step function, tracking cache growth: the call that
        adds a cache entry is the one that paid trace+lower+compile, so its
        wall time is recorded as a compile event (per-fn counter + histogram
        in ``obs``, a ``compile:<fn>`` sub-slice in the step timeline). The
        call runs with ``obs`` pushed as a metrics scope so trace-time
        kernel dispatch counters land in this engine's snapshot too."""
        before = fn._cache_size()
        tr = self.tracer
        t0 = tr.now() if tr is not None else time.perf_counter()
        with obs_metrics.scoped(registry=self.obs):
            out = fn(*args)
        if fn._cache_size() > before:
            t1 = tr.now() if tr is not None else time.perf_counter()
            self.obs.inc("jit_compiles_total", fn=name)
            self.obs.observe("jit_compile_s", t1 - t0, fn=name)
            if tr is not None:
                tr.add_slice(f"compile:{name}", t0, t1)
        return out

    # ---------------- jit'd step functions ----------------

    @contextlib.contextmanager
    def _mesh_ctx(self):
        """Trace context for the jit'd steps: on a mesh, activations follow
        the rules preset (GSPMD) and planned kernels run shard_map'd
        (use_tp); single-device traces are untouched."""
        if self.mesh is None:
            yield
        else:
            with Sh.use_rules(self.mesh, self.rules), \
                    Sh.use_tp(self.mesh, "model"):
                yield

    def _constrain_caches(self, tree):
        """Pin the updated cache tree to the head-wise pool shardings so the
        steady-state jit loop re-feeds identically-sharded (donatable)
        buffers — no resharding and no second compile between steps."""
        if self._cache_specs is None:
            return tree
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            tree, self._cache_specs)

    def _constrain_draft(self, tree):
        if self._draft_cache_specs is None:
            return tree
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            tree, self._draft_cache_specs)

    def _decode_fn(self, params, caches, tables, rings, tokens, pos, active):
        """One token for every slot. tokens (n_slots, 1) int32, pos
        (n_slots,) int32, tables (n_slots, nb_max) int32, rings
        (n_slots, ring_len) int32 or None (static per engine), active
        (n_slots,) bool. Returns (new caches, (n_slots, V) f32 last-token
        logits). kv_splits is a static engine constant: the decode-shaped
        forward walks the KV in chunks when it resolves above 1."""
        with self._mesh_ctx():
            h, new = lm.forward(params, self.cfg, tokens, caches=caches,
                                pos=pos, block_tables=tables,
                                ring_tables=rings,
                                kv_splits=self.kv_splits)
            # inactive / prefilling slots keep their per-slot recurrent state
            new = C.select_slots(caches, new, active)
            logits = lm.logits_fn(params, self.cfg, h)[:, -1]
            return self._constrain_caches(new), logits

    def _prefill_fn(self, params, caches, table_row, ring_row, tokens, start,
                    slot_ix):
        """One prompt chunk for one request. tokens (1, chunk) int32 (pad
        rows zero), start scalar int32 (first row index), slot_ix scalar
        int32 (per-slot recurrent state row). Pad-row K/V falls into the
        null block; per-slot state is sliced/merged around the forward."""
        with self._mesh_ctx():
            sliced = C.slot_slice(caches, slot_ix)
            _, new = lm.forward(params, self.cfg, tokens, caches=sliced,
                                pos=start[None], block_tables=table_row[None],
                                ring_tables=(None if ring_row is None
                                             else ring_row[None]))
            return self._constrain_caches(C.slot_merge(caches, new, slot_ix))

    def _prefill_batched_fn(self, params, caches, tables, rings, tokens,
                            starts):
        """Fixed-shape multi-request chunk. tokens (prefill_batch, chunk)
        int32, starts (prefill_batch,) int32, tables (prefill_batch, nb_max)
        int32. Pad rows carry an all-null table (writes land in the null
        block, outputs discarded). Only valid for archs without per-slot
        state, so the returned tree is the updated pool wholesale."""
        with self._mesh_ctx():
            _, new = lm.forward(params, self.cfg, tokens, caches=caches,
                                pos=starts, block_tables=tables,
                                ring_tables=rings)
            return self._constrain_caches(new)

    def _sample_fn(self, logits, uids, sidx, temperature, top_p):
        """Jit'd decode draw through the sampler stack (one trace for
        greedy AND sampled rows: greedy rows collapse to a one-hot whose
        categorical draw is exactly argmax — see serving/sampler.py)."""
        with self._mesh_ctx():
            return S.sample(logits, self.sampler, uids, sidx, temperature,
                            top_p)

    def _draft_fn(self, dparams, dcaches, tables, rings, first_tok, pos, uids,
                  sidx, temperature, top_p):
        """spec_k+1 drafter steps (lax.scan over one-token forwards against
        the DRAFT cache tree) writing rows pos..pos+spec_k. The scan feeds
        [F[pos], d_1..d_k] — one step more than it samples — so a fully
        accepted round (take = k+1 with the bonus token) still leaves every
        drafter row below the new position holding the token the target
        actually kept; the (k+1)'th sampled token is discarded. Returns
        (new draft caches, drafts (n_slots, k) int32, drafter probs
        (n_slots, k, V) f32). Non-drafting rows ride through on all-null
        tables (their writes and drafts are inert)."""
        base = S.fold_tag(S.request_keys(self.sampler.seed, uids, sidx),
                          S.TAG_DRAFT)
        with self._mesh_ctx():
            def one(carry, i):
                caches, tok = carry
                h, new = lm.forward(dparams, self.draft_cfg,
                                    tok[:, None], caches=caches, pos=pos + i,
                                    block_tables=tables, ring_tables=rings,
                                    kv_splits=self.kv_splits)
                logits = lm.logits_fn(dparams, self.draft_cfg, h)[:, -1]
                p = S.probs(logits, temperature, self.sampler.top_k, top_p)
                keys = jax.vmap(jax.random.fold_in, (0, None))(base, i)
                d = S.draw(p, keys)
                return (self._constrain_draft(new), d), (d, p)
            (dcaches, _), (ds, ps) = jax.lax.scan(
                one, (dcaches, first_tok), jnp.arange(self.spec_k + 1))
        k = self.spec_k
        return dcaches, ds[:k].T, jnp.moveaxis(ps[:k], 0, 1)

    def _verify_fn(self, params, caches, tables, rings, tokens, pos, active):
        """Fixed-shape (n_slots, spec_k+1) TARGET forward over
        [F[pos], d_1..d_k] returning logits at EVERY position — the same
        per-row chunk math as _prefill_batched_fn, just with the hidden
        states kept. The drafts' K/V lands in the target cache as a side
        effect; rows past the accepted prefix hold stale tokens but are
        rewritten by the next round's forward before any emitted query
        attends them (the engine advances pos only over emitted tokens)."""
        with self._mesh_ctx():
            h, new = lm.forward(params, self.cfg, tokens, caches=caches,
                                pos=pos, block_tables=tables,
                                ring_tables=rings)
            new = C.select_slots(caches, new, active)
            logits = lm.logits_fn(params, self.cfg, h)
            return self._constrain_caches(new), logits

    def _draft_prefill_fn(self, dparams, dcaches, tables, rings, tokens,
                          starts):
        """_prefill_batched_fn over the DRAFTER params/cache tree: replays
        chunks of the fed-token stream to catch the drafter's KV up to the
        target's context (after admission, radix full-prefix hits,
        preemption-requeue, or a drafter-KV eviction)."""
        with self._mesh_ctx():
            _, new = lm.forward(dparams, self.draft_cfg, tokens,
                                caches=dcaches, pos=starts,
                                block_tables=tables, ring_tables=rings)
            return self._constrain_draft(new)

    def _spec_accept_fn(self, logits, drafts, p_draft, drafting, uids, sidx,
                        temperature, top_p):
        """Warp the target's (n_slots, spec_k+1, V) logits through the SAME
        sampler stack the plain decode path uses, then run lossless
        rejection sampling (serving/spec.py). Non-drafting rows get zeroed
        drafter probs: zero accepts, and the 'residual' collapses to the
        target's position-0 distribution — a plain decode draw through the
        same trace. Returns (n_acc (n_slots,), tokens (n_slots, k+1))."""
        keys = S.request_keys(self.sampler.seed, uids, sidx)
        p_t = jax.vmap(
            lambda lg: S.probs(lg, temperature, self.sampler.top_k, top_p),
            in_axes=1, out_axes=1)(logits)
        p_d = jnp.where(drafting[:, None, None], p_draft, 0.0)
        return SP.reject_sample(drafts, p_d, p_t,
                                S.fold_tag(keys, S.TAG_ACCEPT),
                                S.fold_tag(keys, S.TAG_RESAMPLE))

    def _prefill_whole_fn(self, params, caches, table_row, ring_row, prompt,
                          slot_ix):
        # legacy-equivalent admission: one full-prompt forward (same math,
        # same float path as the dense batcher), rows scattered into blocks
        # (local layers scatter into the slot's ring when ring-paging is on)
        with self._mesh_ctx():
            _, pf = lm.forward(params, self.cfg, prompt,
                               collect_cache=True)
            return self._constrain_caches(
                C.write_prompt_rows(caches, pf, table_row, slot_ix,
                                    self.block_size, self.cfg.kv_cache_dtype,
                                    pattern=self.cfg.pattern,
                                    ring_table_row=ring_row))

    # ---------------- admission / preemption ----------------

    def _max_blocks_needed(self, P: int, max_new: int) -> int:
        # blocks are only ever allocated for real rows (prefill pad rows
        # land in the null block), so the worst case is the final context
        rows = min(self.max_len, max(P + max_new, P + 1))
        return -(-rows // self.block_size)

    def submit(self, req: Request) -> bool:
        """Admission control: bounded queue + must-fit-alone check (the
        worst case ignores prefix sharing — a cached prefix can be evicted
        before the request runs). Returns False (and marks the request
        rejected) when refused; never blocks."""
        P = int(np.asarray(req.prompt).shape[0])
        if len(self.queue) >= self.max_queue \
                or P > self.max_len - 1 \
                or self._max_blocks_needed(P, req.max_new) > self.n_blocks - 1:
            req.rejected = True
            self.rejections += 1
            if self.tracer is not None:
                self.tracer.on_reject(req.uid, P)
            return False
        self.queue.append(req)
        if self.tracer is not None:
            self.tracer.on_submit(req.uid, P)
        return True

    def _table_row(self, slot: _Slot) -> np.ndarray:
        return C.table_row(slot.blocks, self.nb_max)

    def _note_blocks(self, kind: str, n: int) -> None:
        """Track the high-water per-request pool footprint as a labelled
        gauge ``pool_blocks_peak{kind=...}`` — the signal the long-context
        memory-flattening gate reads (benchmarks/serving.py): target/draft
        peaks grow with context, the ring peak must stay flat."""
        if n > self._peaks.get(kind, 0):
            self._peaks[kind] = n
            self.obs.set_gauge("pool_blocks_peak", n, kind=kind)

    def _ring_row(self, blocks: list) -> Optional[jax.Array]:
        """One slot's ring table row (ring_len,), or None when ring-paging
        is off — the None is a static empty pytree for the jit'd steps, so
        a non-ring engine traces exactly the pre-ring functions."""
        if not self.ring_len:
            return None
        return jnp.asarray(np.asarray(blocks, np.int32))

    def _ring_rows(self, rows_slots, n_rows: int,
                   attr: str = "ring_blocks"):
        """Stacked ring table rows for a fixed-shape batched step:
        ``rows_slots`` pairs (batch row j, slot index i) place slot i's ring
        at row j. Unlisted rows (pad rows, inactive or prefilling slots)
        stay all-null — their writes land in the ring null block, exactly
        mirroring the block-table convention — so an inert batch row can
        never scatter into a live slot's ring."""
        if not self.ring_len:
            return None
        t = np.full((n_rows, self.ring_len), C.NULL_BLOCK, np.int32)
        for j, i in rows_slots:
            b = getattr(self.slots[i], attr)
            if b:
                t[j] = b
        return jnp.asarray(t)

    def _pick_victim(self) -> Optional[int]:
        occupied = [i for i, s in enumerate(self.slots) if s.state != _FREE]
        if not occupied:
            return None
        return min(occupied, key=lambda i: (self.slots[i].req.priority,
                                            -self.slots[i].admit_seq))

    def _preempt(self, ix: int):
        """Evict slot ix: free its blocks and requeue the request with its
        generated tokens folded into the prompt (recompute preemption).
        Blocks the radix tree indexes stay cached (the tree holds its own
        reference), so the re-prefill usually resumes past them."""
        s = self.slots[ix]
        req = s.req
        req.n_preempted += 1
        self.preemptions += 1
        if s.blocks:
            self.pool.free(s.blocks)
        if s.draft_blocks:
            self.pool.free(s.draft_blocks)
        if s.ring_blocks:
            self.ring_pool.free(s.ring_blocks)
        if s.draft_ring_blocks:
            self.ring_pool.free(s.draft_ring_blocks)
        self.slots[ix] = _Slot()
        self.queue.appendleft(req)
        if self.tracer is not None:
            self.tracer.on_preempt(req.uid)

    def _make_room(self, n: int, requester_ix: int) -> bool:
        """Free blocks until n are available: LRU-evict unreferenced radix-
        cached blocks first (free — no live request is harmed), then preempt
        victims. Returns False if the requester itself was evicted (it is
        the lowest-priority occupant)."""
        while self.pool.n_free < n:
            if self.radix is not None:
                with self._phase("evict"):
                    evicted = self.radix.evict_one()
                if evicted:
                    continue
            if self._evict_one_draft():
                continue                     # drafter KV goes before any
            victim = self._pick_victim()     # live request is preempted
            if victim is None:
                return False
            with self._phase("preempt"):
                self._preempt(victim)
            if victim == requester_ix:
                return False
        return True

    def _evict_one_draft(self) -> bool:
        """Reclaim one slot's entire drafter KV (largest holding first).
        The drafter is a pure accelerator: dropping its cache loses no
        request state — the slot just decodes un-speculated until the
        catch-up prefill rebuilds it. No-op (False) when nothing to take."""
        cand = [i for i, s in enumerate(self.slots) if s.draft_blocks]
        if not cand:
            return False
        s = self.slots[max(cand,
                           key=lambda j: len(self.slots[j].draft_blocks))]
        self.pool.free(s.draft_blocks)
        s.draft_blocks = []
        s.draft_done = 0
        self.spec_draft_evictions += 1
        return True

    def _alloc_draft(self, ix: int, n: int) -> bool:
        """Allocate n drafter blocks for slot ix WITHOUT preempting anyone:
        LRU-evict unreferenced radix blocks, then give up (the slot simply
        doesn't draft / catch up this round). Target allocations always win
        over drafter ones — _make_room reclaims drafter KV, this never
        takes a live request's blocks."""
        while self.pool.n_free < n:
            if self.radix is not None and self.radix.evict_one():
                continue
            return False
        self.slots[ix].draft_blocks += self.pool.alloc(n)
        self._note_blocks("draft", len(self.slots[ix].draft_blocks))
        return True

    def _free_ix(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s.state == _FREE:
                return i
        return None

    def _admit(self):
        """Move queued requests into free slots while first-chunk blocks are
        available. With the radix cache on, the effective prompt's longest
        cached block-aligned prefix is attached by refcount bump and prefill
        starts after it; admission may LRU-evict unreferenced cached blocks
        but never preempts a running request."""
        while self.queue:
            ix = self._free_ix()
            if ix is None:
                return
            req = self.queue[0]
            eff_prompt = np.concatenate(
                [np.asarray(req.prompt, np.int32).reshape(-1),
                 np.asarray(req.out, np.int32)])
            P = len(eff_prompt)
            shared: list[int] = []
            if self.radix is not None and P > 0:
                shared = self.radix.match(eff_prompt)
            m = len(shared) * self.block_size
            first_blocks = self._first_alloc_size(P, m)
            while self.radix is not None and first_blocks > self.pool.n_free:
                with self._phase("evict"):   # eviction racing admission
                    evicted = self.radix.evict_one()
                if not evicted:
                    break
            if first_blocks > self.pool.n_free:
                if shared:
                    self.pool.free(shared)   # release the match's references
                return                       # wait for blocks to free up
            self.queue.popleft()
            self._admit_counter += 1
            self.prefill_tokens_shared += m
            if self.radix is not None:
                self.radix.hit_tokens += m
                self.radix.miss_tokens += P - m
            slot = _Slot(req=req, prompt=eff_prompt, pos=0, prefill_done=m,
                         blocks=list(shared), admit_seq=self._admit_counter)
            if self.ring_len:
                # dedicated pool sized for every slot: alloc cannot fail
                slot.ring_blocks = self.ring_pool.alloc(self.ring_len)
                if self.spec:
                    slot.draft_ring_blocks = \
                        self.ring_pool.alloc(self.ring_len)
                self._note_blocks("ring", self.ring_len)
            if slot.blocks:
                self._note_blocks("target", len(slot.blocks))
            self.slots[ix] = slot
            if self.tracer is not None:
                self.tracer.on_admit(req.uid, shared_tokens=m)
            if self._has_state:
                self.caches = self._run_jit(
                    "reset_slot", self._reset, self.caches,
                    jnp.asarray(ix, jnp.int32))
            if P == 0:
                slot.state = _DECODE         # zero-block request
                slot.next_input = 0
            elif m >= P:
                slot.state = _DECODE         # full-prefix hit: skip prefill
                slot.prefill_done = P
                slot.pos = P
                slot.next_input = int(eff_prompt[-1])
            elif self.prefill_mode == "whole":
                slot.state = _PREFILL        # visible to _pick_victim
                self._do_whole_prefill(ix)
                if self.slots[ix].req is not req:
                    break                    # admission failed (self-evicted)
            else:
                slot.state = _PREFILL

    def _first_alloc_size(self, P: int, shared: int = 0) -> int:
        """Blocks the first prefill chunk needs beyond `shared` attached
        prefix tokens (shared is always block-aligned)."""
        if P == 0:
            return 1
        if shared >= P:
            return 0
        if self.prefill_mode == "whole":
            return -(-P // self.block_size)
        rows = shared + min(self.chunk_size, P - shared)
        return -(-rows // self.block_size) - shared // self.block_size

    # ---------------- prefill ----------------

    def _do_whole_prefill(self, ix: int):
        s = self.slots[ix]
        P = len(s.prompt)
        need = -(-P // self.block_size) - len(s.blocks)
        if need > 0:
            if not self._make_room(need, ix):
                return
            s.blocks += self.pool.alloc(need)
            self._note_blocks("target", len(s.blocks))
        tr = self.tracer
        t0 = tr.now() if tr is not None else 0.0
        self.caches = self._run_jit(
            "prefill_whole", self._prefill_whole,
            self.params, self.caches, jnp.asarray(self._table_row(s)),
            self._ring_row(s.ring_blocks),
            jnp.asarray(s.prompt, jnp.int32)[None],
            jnp.asarray(ix, jnp.int32))
        if tr is not None:
            tr.on_prefill_chunk(s.req.uid, start=0, rows=P, t0=t0,
                                t1=tr.now())
        self.prefill_tokens_computed += P
        s.state = _DECODE
        s.prefill_done = P
        s.pos = P
        s.next_input = int(s.prompt[-1])

    def _prep_chunk(self, ix: int):
        """Host-side half of a chunk: pick bounds, ensure blocks (possibly
        preempting), build the padded token row. Returns (tokens (length,),
        start, real) or None if the slot was evicted while making room."""
        s = self.slots[ix]
        P = len(s.prompt)
        start = s.prefill_done
        if self._has_state:
            # recurrent state must see exactly the prompt: no pad tokens
            length = min(self.chunk_size, P - start)
        else:
            length = self.chunk_size          # fixed shape; pad rows inert
        real = min(length, P - start)
        # blocks cover real rows only: pad-row writes beyond the table's
        # allocated entries fall into the null block (never read)
        need = -(-(start + real) // self.block_size) - len(s.blocks)
        if need > 0:
            if not self._make_room(need, ix):
                return None                   # self-preempted
            s.blocks += self.pool.alloc(need)
            self._note_blocks("target", len(s.blocks))
        chunk = np.zeros((length,), np.int32)
        chunk[:real] = s.prompt[start:start + real]
        return chunk, start, real

    def _finish_chunk(self, ix: int, real: int):
        """Advance bookkeeping after a chunk ran: index newly completed full
        prompt blocks in the radix tree, flip to decode when done."""
        s = self.slots[ix]
        s.prefill_done += real
        self.prefill_tokens_computed += real
        if self.radix is not None:
            s.radix_node, s.radix_done = self.radix.insert(
                s.prompt[:s.prefill_done], s.blocks,
                at=s.radix_node, done=s.radix_done)
        if s.prefill_done >= len(s.prompt):
            s.state = _DECODE
            s.pos = len(s.prompt)
            s.next_input = int(s.prompt[-1])

    def _do_prefill_chunk(self, ix: int):
        prep = self._prep_chunk(ix)
        if prep is None:
            return
        chunk, start, real = prep
        s = self.slots[ix]
        tr = self.tracer
        t0 = tr.now() if tr is not None else 0.0
        self.caches = self._run_jit(
            "prefill_chunk", self._prefill_chunk,
            self.params, self.caches, jnp.asarray(self._table_row(s)),
            self._ring_row(s.ring_blocks), jnp.asarray(chunk)[None],
            jnp.asarray(start, jnp.int32), jnp.asarray(ix, jnp.int32))
        if tr is not None:
            tr.on_prefill_chunk(s.req.uid, start=start, rows=real, t0=t0,
                                t1=tr.now())
        self.prefill_chunks += 1
        self._finish_chunk(ix, real)

    def _do_prefill_batched(self, ixs: list[int]):
        """Run one fused chunk over up to prefill_batch prefilling slots.
        Pad rows (fewer live slots than prefill_batch) get an all-null
        table: their writes land in the null block and their outputs are
        never read."""
        preps = []
        for ix in ixs:
            s = self.slots[ix]
            if s.state != _PREFILL:
                continue                      # evicted by an earlier prep
            req = s.req
            prep = self._prep_chunk(ix)
            if prep is not None:
                preps.append((ix, req, prep))
        # a later slot's _make_room may have preempted an earlier prepped
        # slot; only launch rows whose slot still holds the same request
        live = [(ix, prep) for ix, req, prep in preps
                if self.slots[ix].state == _PREFILL
                and self.slots[ix].req is req]
        if not live:
            return
        Bp = self.prefill_batch
        tokens = np.zeros((Bp, self.chunk_size), np.int32)
        starts = np.zeros((Bp,), np.int32)
        tables = np.full((Bp, self.nb_max), C.NULL_BLOCK, np.int32)
        for j, (ix, (chunk, start, _)) in enumerate(live):
            tokens[j] = chunk
            starts[j] = start
            tables[j] = self._table_row(self.slots[ix])
        tr = self.tracer
        t0 = tr.now() if tr is not None else 0.0
        self.caches = self._run_jit(
            "prefill_batched", self._prefill_batched,
            self.params, self.caches, jnp.asarray(tables),
            self._ring_rows([(j, ix) for j, (ix, _) in enumerate(live)], Bp),
            jnp.asarray(tokens), jnp.asarray(starts))
        if tr is not None:
            t1 = tr.now()
            for ix, (chunk, start, real) in live:
                tr.on_prefill_chunk(self.slots[ix].req.uid, start=start,
                                    rows=real, t0=t0, t1=t1)
        self.prefill_chunks += 1
        for ix, (_, _, real) in live:
            self._finish_chunk(ix, real)

    # ---------------- decode ----------------

    def _grow_for_decode(self):
        """Ensure every decoding slot owns the block its next row lands in,
        preempting (possibly the slot itself) on pool exhaustion."""
        for i in range(self.n_slots):
            s = self.slots[i]
            if s.state != _DECODE:
                continue
            need = s.pos // self.block_size + 1 - len(s.blocks)
            if need > 0:
                if not self._make_room(need, i):
                    continue                  # slot i was evicted
                s.blocks += self.pool.alloc(need)
                self._note_blocks("target", len(s.blocks))

    def _finish(self, ix: int):
        s = self.slots[ix]
        s.req.done = True
        if s.blocks:
            self.pool.free(s.blocks)
        if s.draft_blocks:
            self.pool.free(s.draft_blocks)
        if s.ring_blocks:
            self.ring_pool.free(s.ring_blocks)
        if s.draft_ring_blocks:
            self.ring_pool.free(s.draft_ring_blocks)
        self.slots[ix] = _Slot()
        if self.tracer is not None:
            self.tracer.on_finish(s.req.uid)

    def _do_decode(self):
        self._grow_for_decode()
        active = [i for i, s in enumerate(self.slots) if s.state == _DECODE]
        if not active:
            return
        tokens = jnp.asarray(
            [[s.next_input if s.state == _DECODE else 0] for s in self.slots],
            jnp.int32)
        pos = jnp.asarray(
            [s.pos if s.state == _DECODE else 0 for s in self.slots],
            jnp.int32)
        tables = np.zeros((self.n_slots, self.nb_max), np.int32)
        for i in active:
            tables[i] = self._table_row(self.slots[i])
        mask = np.zeros((self.n_slots,), bool)
        mask[active] = True
        self.caches, logits = self._run_jit(
            "decode", self._decode,
            self.params, self.caches, jnp.asarray(tables),
            self._ring_rows([(i, i) for i in active], self.n_slots),
            tokens, pos, jnp.asarray(mask))
        if self.sample is not None:
            nxt = self.sample(logits)        # legacy host-side hook
        else:
            uids, sidx, temp, topp = self._sampler_rows()
            nxt = self._run_jit("sample", self._sample, logits, uids, sidx,
                                temp, topp)

        self.decode_steps += 1
        self.busy_slot_steps += len(active)
        for i in active:
            s = self.slots[i]
            tok = int(nxt[i])
            req = s.req
            req.out.append(tok)
            s.next_input = tok
            s.pos += 1
            done = ((req.eos_id is not None and tok == req.eos_id)
                    or len(req.out) >= req.max_new
                    or s.pos >= self.max_len - 1)
            if self.tracer is not None:
                self.tracer.on_token(req.uid, tok, done)
            if req.on_token is not None:
                req.on_token(tok, done)
            if done:
                self._finish(i)

    def _sampler_rows(self):
        """(uids, sidx, temperature, top_p) rows for the jit'd sampler:
        per-request overrides folded over the engine defaults, plus the
        PRNG derivation inputs (uid, sample index = tokens generated so
        far — see serving/sampler.py). Inactive slots get inert values;
        their draws are discarded. Non-int uids hash through crc32 so the
        stream id stays stable across runs."""
        sc = self.sampler
        uids = np.zeros((self.n_slots,), np.int32)
        sidx = np.zeros((self.n_slots,), np.int32)
        temp = np.full((self.n_slots,), sc.temperature, np.float32)
        topp = np.full((self.n_slots,), sc.top_p, np.float32)
        for i, s in enumerate(self.slots):
            r = s.req
            if r is None:
                continue
            u = r.uid if isinstance(r.uid, int) \
                else zlib.crc32(str(r.uid).encode())
            uids[i] = np.int64(u) & 0x7FFFFFFF
            sidx[i] = len(r.out)
            if r.temperature is not None:
                temp[i] = r.temperature
            if r.top_p is not None:
                topp[i] = r.top_p
        return (jnp.asarray(uids), jnp.asarray(sidx), jnp.asarray(temp),
                jnp.asarray(topp))

    # ---------------- speculative decode ----------------

    def _fed_stream(self, s: _Slot, upto: int) -> np.ndarray:
        """First `upto` entries of the slot's fed-token stream F — the
        exact sequence of input tokens whose K/V occupies target rows
        0..upto-1: the prompt, then the last prompt token re-fed at row P
        (the first decode step's input), then the generated tokens. The
        drafter's catch-up prefill replays this stream so drafter rows
        below draft_done always mirror the target's context byte-for-byte
        (same tokens, same positions — only the weights differ)."""
        P = len(s.prompt)
        f = list(s.prompt[:min(upto, P)])
        if upto > P:
            f.append(int(s.prompt[-1]) if P else 0)
            # tokens generated SINCE ADMISSION (earlier generations were
            # folded into s.prompt by recompute preemption): pos - P of them
            gen = s.req.out[len(s.req.out) - (s.pos - P):] if s.pos > P \
                else []
            f.extend(int(t) for t in gen[: upto - P - 1])
        return np.asarray(f, np.int32)

    def _draft_target(self, s: _Slot) -> int:
        """Row the drafter should be caught up to: the filled prompt rows
        while prefilling, the decode position afterwards."""
        return s.prefill_done if s.state == _PREFILL else s.pos

    def _do_draft_prefill(self):
        """One fixed-shape batched chunk catching drafter KV up to the
        target's context, for up to prefill_batch lagging slots (round-
        robin). Runs every step alongside target prefill, so the drafter is
        usually synced by the time a request reaches decode; slots it
        cannot serve (no free blocks) keep decoding un-speculated."""
        lag = [i for i, s in enumerate(self.slots)
               if s.state in (_PREFILL, _DECODE)
               and s.draft_done < self._draft_target(s)]
        if not lag:
            return
        j0 = self._dpf_rr % len(lag)
        self._dpf_rr += 1
        lag = (lag[j0:] + lag[:j0])[:self.prefill_batch]
        Bp = self.prefill_batch
        tokens = np.zeros((Bp, self.chunk_size), np.int32)
        starts = np.zeros((Bp,), np.int32)
        tables = np.full((Bp, self.nb_spec), C.NULL_BLOCK, np.int32)
        rings = np.full((Bp, max(self.ring_len, 1)), C.NULL_BLOCK, np.int32)
        live = []
        for j, i in enumerate(lag):
            s = self.slots[i]
            start = s.draft_done
            real = min(self.chunk_size, self._draft_target(s) - start)
            need = -(-(start + real) // self.block_size) \
                - len(s.draft_blocks)
            if need > 0 and not self._alloc_draft(i, need):
                continue                      # row stays inert (all-null)
            tokens[j, :real] = self._fed_stream(s, start + real)[start:]
            starts[j] = start
            tables[j] = C.table_row(s.draft_blocks, self.nb_spec)
            if self.ring_len:
                rings[j] = s.draft_ring_blocks
            live.append((i, real))
        if not live:
            return
        self.draft_caches = self._run_jit(
            "draft_prefill", self._draft_prefill,
            self.draft_params, self.draft_caches, jnp.asarray(tables),
            jnp.asarray(rings) if self.ring_len else None,
            jnp.asarray(tokens), jnp.asarray(starts))
        for i, real in live:
            self.slots[i].draft_done += real

    def _do_spec_decode(self):
        """One speculative round for the whole decode batch: drafter scans
        spec_k+1 one-token steps, the target verifies [F[pos], d_1..d_k] in
        one (n_slots, k+1) forward, rejection sampling (serving/spec.py)
        decides how many tokens each slot emits (1..k+1). Slots whose
        drafter is not synced (or that can't get blocks) ride the SAME two
        traces un-speculated — zeroed drafter probs make the accept step a
        plain decode draw — so a steady-state spec engine runs exactly
        these jit entries every step, never a per-state variant."""
        k = self.spec_k
        self._grow_for_decode()
        # who drafts this round: synced drafter + target blocks covering
        # verify rows pos..pos+k + drafter blocks for the same rows; any
        # failure just means the slot runs un-speculated (1 token)
        drafting = np.zeros((self.n_slots,), bool)
        for i in range(self.n_slots):
            s = self.slots[i]
            if s.state != _DECODE or s.draft_done != s.pos:
                continue
            rows = min(s.pos + k + 1, self.max_len)
            need = -(-rows // self.block_size) - len(s.blocks)
            if need > 0:
                if not self._make_room(need, i):
                    continue                 # slot i itself was evicted
                s.blocks += self.pool.alloc(need)
                self._note_blocks("target", len(s.blocks))
            dneed = -(-rows // self.block_size) - len(s.draft_blocks)
            if dneed > 0 and not self._alloc_draft(i, dneed):
                continue
            drafting[i] = True
        # _make_room above may have preempted earlier-marked slots
        active = [i for i, s in enumerate(self.slots) if s.state == _DECODE]
        for i in range(self.n_slots):
            if drafting[i] and self.slots[i].state != _DECODE:
                drafting[i] = False
        if not active:
            return
        first = np.zeros((self.n_slots,), np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        vtables = np.full((self.n_slots, self.nb_spec), C.NULL_BLOCK,
                          np.int32)
        dtables = np.full((self.n_slots, self.nb_spec), C.NULL_BLOCK,
                          np.int32)
        drings = np.full((self.n_slots, max(self.ring_len, 1)),
                         C.NULL_BLOCK, np.int32)
        mask = np.zeros((self.n_slots,), bool)
        uids, sidx, temp, topp = self._sampler_rows()
        for i in active:
            s = self.slots[i]
            first[i] = s.next_input
            pos[i] = s.pos
            vtables[i] = C.table_row(s.blocks, self.nb_spec)
            mask[i] = True
            if drafting[i]:
                dtables[i] = C.table_row(s.draft_blocks, self.nb_spec)
                if self.ring_len:
                    # non-drafting rows keep an all-null ring row: their
                    # inert scan writes must not plant rows in a draft
                    # ring a catch-up replay is still filling
                    drings[i] = s.draft_ring_blocks

        self.draft_caches, drafts, p_draft = self._run_jit(
            "draft", self._draft, self.draft_params, self.draft_caches,
            jnp.asarray(dtables),
            jnp.asarray(drings) if self.ring_len else None,
            jnp.asarray(first), jnp.asarray(pos), uids, sidx, temp, topp)
        vtokens = jnp.concatenate([jnp.asarray(first)[:, None], drafts],
                                  axis=1)
        self.caches, logits = self._run_jit(
            "verify", self._verify, self.params, self.caches,
            jnp.asarray(vtables),
            self._ring_rows([(i, i) for i in active], self.n_slots),
            vtokens, jnp.asarray(pos), jnp.asarray(mask))
        n_acc, toks = self._run_jit(
            "spec_accept", self._spec_accept, logits, drafts, p_draft,
            jnp.asarray(drafting), uids, sidx, temp, topp)
        n_acc = np.asarray(n_acc)
        toks = np.asarray(toks)

        self.decode_steps += 1
        self.spec_rounds += 1
        self.busy_slot_steps += len(active)
        for i in active:
            s = self.slots[i]
            req = s.req
            # cap the emitted block: context room keeps every emitted row
            # strictly inside real blocks (the widened tables' null-block
            # overflow is never attended by an emitted token's query)
            limit = min(int(n_acc[i]) + 1,
                        (self.max_len - 1) - s.pos,
                        req.max_new - len(req.out))
            if drafting[i]:
                self.spec_draft_tokens += k
            emitted, done = 0, False
            for j in range(limit):
                tok = int(toks[i, j])
                req.out.append(tok)
                s.next_input = tok
                s.pos += 1
                emitted += 1
                done = ((req.eos_id is not None and tok == req.eos_id)
                        or len(req.out) >= req.max_new
                        or s.pos >= self.max_len - 1)
                if self.tracer is not None:
                    self.tracer.on_token(req.uid, tok, done)
                if req.on_token is not None:
                    req.on_token(tok, done)
                if done:
                    break
            self.spec_emitted += emitted
            if drafting[i]:
                self.spec_accepted += min(int(n_acc[i]), emitted)
                # every emitted token below the new pos was fed to the
                # drafter at the same row by the k+1-step scan (accepted
                # drafts verbatim; the resample/bonus row sits AT the new
                # pos and is overwritten by the next round's first step)
                s.draft_done = s.pos
            if done:
                self._finish(i)

    # ---------------- main loop ----------------

    def step(self) -> int:
        """Admit, run one prefill chunk step (batched over up to
        prefill_batch requests), run one batched decode step. Returns the
        number of occupied slots. Streaming callbacks fire from inside this
        call, in generation order. With a tracer attached, the step is
        decomposed into admit / prefill / decode phases (evict / preempt /
        compile nested inside whichever triggered them), pool/queue
        gauges are sampled at step end, and the step is a
        ``StepTraceAnnotation`` ``engine.step`` on the profiler's trace."""
        tr = self.tracer
        if tr is None:
            return self._step()
        with jax.profiler.StepTraceAnnotation("engine.step",
                                              step_num=self.steps):
            tr.step_begin(self.steps)
            n = self._step()
            tr.step_end(self._sample_gauges())
        return n

    def _step(self) -> int:
        with self._phase("admit"):
            self._admit()
        prefilling = [i for i, s in enumerate(self.slots)
                      if s.state == _PREFILL]
        if prefilling:
            k = self._pf_rr % len(prefilling)
            self._pf_rr += 1
            with self._phase("prefill"):
                if self.prefill_batch > 1:
                    sel = (prefilling[k:]
                           + prefilling[:k])[:self.prefill_batch]
                    self._do_prefill_batched(sel)
                else:
                    self._do_prefill_chunk(prefilling[k])
        if self.spec:
            with self._phase("draft_prefill"):
                self._do_draft_prefill()
        with self._phase("decode"):
            if self.spec:
                self._do_spec_decode()
            else:
                self._do_decode()
        self.steps += 1
        return sum(s.state != _FREE for s in self.slots)

    def run(self, max_steps: int = 10_000) -> dict:
        """Step until the queue and all slots drain (or max_steps); returns
        `metrics()`."""
        while (self.queue or any(s.state != _FREE for s in self.slots)) \
                and self.steps < max_steps:
            self.step()
        return self.metrics()

    def reset_prefix_cache(self):
        """Invalidate the radix index (e.g. after swapping params). Cached
        blocks not attached to a live request return to the free list;
        in-flight requests are unaffected. No-op when sharing is off."""
        if self.radix is not None:
            self.radix.reset()
            for s in self.slots:        # resume hints point into the old tree
                s.radix_node, s.radix_done = None, 0

    def _sample_gauges(self, mirror: bool = False) -> dict:
        """Per-step gauges: pool occupancy, tree-held blocks, scheduler
        load, and the cumulative radix hit ratio. ``mirror=True`` also
        writes them into ``obs`` as last-value gauges — done once at
        ``metrics()`` time, not per step (six locked registry writes per
        step were measurable against sub-ms step times)."""
        free = self.pool.n_free
        g = {
            "free_blocks": free,
            "used_blocks": self.n_blocks - 1 - free,
            "tree_blocks": (self.radix.n_nodes
                            if self.radix is not None else 0),
            "active_slots": sum(s.state != _FREE for s in self.slots),
            "queue_depth": len(self.queue),
            "radix_hit_ratio": None,
        }
        if self.radix is not None:
            seen = self.radix.hit_tokens + self.radix.miss_tokens
            if seen:
                g["radix_hit_ratio"] = self.radix.hit_tokens / seen
        if mirror:
            for k, v in g.items():
                if v is not None:
                    self.obs.set_gauge(k, v)
        return g

    def metrics(self) -> dict:
        util = self.busy_slot_steps / max(self.decode_steps * self.n_slots, 1)
        self._sample_gauges(mirror=True)
        self.obs.set_gauge("jit_cache_entries", self.n_compiles())
        out = {
            "steps": self.decode_steps,
            "engine_steps": self.steps,
            "decode_steps": self.decode_steps,
            "prefill_chunks": self.prefill_chunks,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefill_tokens_shared": self.prefill_tokens_shared,
            "preemptions": self.preemptions,
            "rejections": self.rejections,
            "slot_utilization": util,
            "prefix_cache": (self.radix.metrics()
                             if self.radix is not None else None),
            "n_compiles": self.n_compiles(),
            # high-water per-request pool footprint by kind (also a labelled
            # obs gauge pool_blocks_peak{kind=...}): the long-context bench
            # gates on the ring peak staying flat as contexts grow
            "pool_blocks_peak": dict(self._peaks),
            "spec": None if not self.spec else {
                "rounds": self.spec_rounds,
                "draft_tokens": self.spec_draft_tokens,
                "accepted": self.spec_accepted,
                "emitted": self.spec_emitted,
                "acceptance_rate": (self.spec_accepted
                                    / max(self.spec_draft_tokens, 1)),
                # per SLOT-step (1.0 == plain decode; up to spec_k+1)
                "accepted_tokens_per_step": (self.spec_emitted
                                             / max(self.busy_slot_steps, 1)),
                "draft_evictions": self.spec_draft_evictions,
            },
            # unified registry snapshot (counters above + compile tracking
            # + last-sampled gauges), flat name{label=value} keys
            "metrics": self.obs.snapshot(),
        }
        if self.tracer is not None:
            out["latency"] = self.tracer.latency_summary()
            out["phases"] = self.tracer.phase_summary()
        return out

    def per_device_weight_bytes(self) -> int:
        """Parameter bytes resident on ONE device (the first mesh device).
        With a TP mesh this is ~1/N of the replicated footprint for every
        dividing dim — the memory half of the tensor-parallel contract."""
        dev = (self.mesh.devices.flat[0] if self.mesh is not None
               else jax.devices()[0])
        total = 0
        for x in jax.tree.leaves(self.params):
            if not hasattr(x, "addressable_shards"):
                continue
            for s in x.addressable_shards:
                if s.device == dev:
                    total += s.data.size * s.data.dtype.itemsize
        return total

    def n_compiles(self) -> int:
        """Total jit cache entries across the engine's step functions (the
        no-recompilation-between-steps check in benchmarks/serving.py)."""
        fns = [self._decode, self._prefill_chunk, self._prefill_batched,
               self._prefill_whole, self._reset, self._sample]
        if self.spec:
            fns += [self._draft, self._verify, self._draft_prefill,
                    self._spec_accept]
        return sum(f._cache_size() for f in fns)
