"""Three-term roofline from compiled dry-run artifacts (TPU v5e target).

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HBM_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

Hardware constants (v5e): 197 TFLOP/s bf16 per chip; 819 GB/s HBM;
~50 GB/s/link ICI (per the assignment).

Sources, and one honest caveat: XLA's ``compiled.cost_analysis()`` counts a
``while`` body ONCE regardless of trip count (verified in this container —
a lax.scan of 8 matmuls reports 1/8 the flops of its unrolled twin). All our
big models scan over layer superblocks and attention chunks, so raw
cost_analysis under-counts by >10x. We therefore parse the post-optimization
HLO text (``compiled.as_text()``): build the computation call graph, extract
while-loop trip counts from their condition computations, and multiply every
``dot`` op's FLOPs and every collective's bytes by the product of enclosing
trip counts. ``benchmarks/hlo_validation.py`` cross-checks this parser
against cost_analysis on fully-unrolled reduced models (agreement within a
few % — elementwise flops are the residual).

The memory term uses a documented analytic traffic model (params/cache/
activation bytes actually moved per step) because "bytes accessed" from
cost_analysis has the same while-undercount plus fusion ambiguity.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

# ---- TPU v5e constants (assignment-specified) ----
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~per-direction)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def shape_bytes(shape_str: str) -> int:
    """Total bytes of all array shapes inside a (possibly tuple) shape str."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# --------------------------------------------------------------------------- #
# HLO text parsing
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class HloStats:
    dot_flops: float = 0.0
    collective_bytes: dict = dataclasses.field(default_factory=dict)
    n_collectives: dict = dataclasses.field(default_factory=dict)
    n_while: int = 0
    unknown_trip_counts: int = 0

    @property
    def total_collective_bytes(self) -> float:
        return float(sum(self.collective_bytes.values()))


def _split_shape_token(rest: str) -> tuple[str, str]:
    """Leading shape token (handles tuple shapes with nested parens)."""
    rest = rest.lstrip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rest[: i + 1], rest[i + 1:]
        return rest, ""
    i = rest.find(" ")
    return (rest, "") if i < 0 else (rest[:i], rest[i:])


_OP_RE = re.compile(r"^(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_HDR_RE = re.compile(r"^(ENTRY\s+)?%([\w\.\-]+)\s*\(")


def _split_computations(hlo: str) -> tuple[dict[str, list[str]], Optional[str]]:
    """computation name -> op lines; also returns the ENTRY name."""
    comps: dict[str, list[str]] = {}
    entry: Optional[str] = None
    cur: Optional[str] = None
    for line in hlo.splitlines():
        ls = line.rstrip()
        if not ls:
            continue
        if not line.startswith(" "):
            m = _HDR_RE.match(ls)
            if m and ls.endswith("{"):
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
            else:
                cur = None
            continue
        if cur is not None:
            comps[cur].append(ls.strip())
    return comps, entry


def _parse_op(line: str):
    """-> (name, shape_str, opcode, args_str) or None."""
    m = _OP_RE.match(line)
    if not m:
        return None
    name, rest = m.group(1), m.group(2)
    shape, rest = _split_shape_token(rest)
    rest = rest.lstrip()
    p = rest.find("(")
    if p < 0:
        return None
    opcode = rest[:p].strip()
    return name, shape, opcode, rest[p + 1:]


def _operand_names(args: str) -> list[str]:
    """First-level operand names from an op's argument text."""
    # brackets/braces nest too: some jax versions print operands with inline
    # shapes+layouts ("f32[64,128]{1,0} %name") whose commas must not split
    out, depth, cur = [], 0, ""
    for ch in args:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if ch == ")" and depth == 0:
                break
            depth -= 1
        if ch == "," and depth == 0:
            out.append(cur)
            cur = ""
        else:
            cur += ch
    if cur.strip():
        out.append(cur)
    names = []
    for o in out:
        mm = re.search(r"%([\w\.\-]+)", o)
        names.append(mm.group(1) if mm else "")
    return names


def _shape_dims(shape_str: str) -> list[int]:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _trip_count(cond_lines: list[str]) -> Optional[int]:
    """Scan-style cond: ROOT uses compare(iv, const)/fused compare; the s32[]
    constant in the cond computation is the trip count."""
    consts: dict[str, int] = {}
    for ln in cond_lines:
        p = _parse_op(ln)
        if p and p[2] == "constant" and p[1].startswith("s32[]"):
            m = re.match(r"(\-?\d+)", p[3])
            if m:
                consts[p[0]] = int(m.group(1))
    if not consts:
        return None
    root_ops: list[str] = []
    for ln in cond_lines:
        if ln.startswith("ROOT"):
            p = _parse_op(ln)
            if p:
                root_ops = _operand_names(p[3])
    for n in root_ops:
        if n in consts:
            return consts[n]
    if len(consts) == 1:
        return next(iter(consts.values()))
    return max(consts.values())


def parse_hlo(hlo: str, *, bf16_model: bool = False) -> HloStats:
    """bf16_model: the jax program computes in bf16 but XLA:CPU float-
    normalization promotes bf16 buffers/reductions to f32 before SPMD ops —
    f32 collective payloads >= 1 MiB are halved to reflect the TPU (bf16)
    program. Verified at the StableHLO level (no f32 collectives pre-XLA)."""
    comps, entry = _split_computations(hlo)
    stats = HloStats()

    # global symbol table: op result name -> shape string
    shapes: dict[str, str] = {}
    parsed_comps: dict[str, list] = {}
    for cname, lines in comps.items():
        plist = []
        for ln in lines:
            p = _parse_op(ln)
            if p is not None:
                shapes[p[0]] = p[1]
                plist.append(p)
        parsed_comps[cname] = plist

    # call graph with loop multipliers
    children: dict[str, list[tuple[str, float]]] = {c: [] for c in comps}
    for cname, plist in parsed_comps.items():
        for (name, shape, opcode, args) in plist:
            if opcode == "while":
                b = re.search(r"body=%?([\w\.\-]+)", args)
                c = re.search(r"condition=%?([\w\.\-]+)", args)
                trip = None
                if c and c.group(1) in comps:
                    trip = _trip_count(comps[c.group(1)])
                if trip is None:
                    trip = 1
                    stats.unknown_trip_counts += 1
                stats.n_while += 1
                if b and b.group(1) in comps:
                    children[cname].append((b.group(1), float(max(trip, 1))))
                if c and c.group(1) in comps:
                    children[cname].append((c.group(1), 0.0))  # cond: tiny, skip
            else:
                for key in ("calls=", "to_apply=", "then_computation=",
                            "else_computation="):
                    for m in re.finditer(key + r"%?([\w\.\-]+)", args):
                        if m.group(1) in comps:
                            children[cname].append((m.group(1), 1.0))
                m = re.search(r"branch_computations=\{([^}]*)\}", args)
                if m:
                    for b in m.group(1).split(","):
                        b = b.strip().lstrip("%")
                        if b in comps:
                            children[cname].append((b, 1.0))

    if entry is None:
        referenced = {b for v in children.values() for (b, _) in v}
        roots = [c for c in comps if c not in referenced]
        entry = roots[0] if roots else next(iter(comps))

    mult: dict[str, float] = {c: 0.0 for c in comps}

    def visit(c, m):
        mult[c] += m
        for (b, t) in children.get(c, []):
            if m * t > 0:
                visit(b, m * t)

    visit(entry, 1.0)

    for cname, plist in parsed_comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        for (name, shape, opcode, args) in plist:
            if opcode == "dot":
                ops = _operand_names(args)
                lhs_dims = _shape_dims(shapes.get(ops[0], "")) if ops else []
                out_dims = _shape_dims(shape)
                k = 1
                km = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", args)
                if km and km.group(1) and lhs_dims:
                    for ix in km.group(1).split(","):
                        if int(ix) < len(lhs_dims):
                            k *= lhs_dims[int(ix)]
                n_out = 1
                for d in out_dims:
                    n_out *= d
                stats.dot_flops += m * 2.0 * n_out * k
            else:
                for coll in _COLLECTIVES:
                    if opcode == coll or opcode == coll + "-start":
                        factor = 2.0 if coll == "all-reduce" else 1.0
                        b = shape_bytes(shape)
                        # XLA:CPU float-normalization promotes bf16 reductions
                        # to f32 (to_apply=%..._promoted); the TPU program
                        # reduces in bf16. Halve promoted payloads >= 1 MiB.
                        if ("f32[" in shape and b >= 1 << 20
                                and ("promoted" in args or bf16_model)):
                            b *= 0.5
                        b = b * factor * m
                        stats.collective_bytes[coll] = (
                            stats.collective_bytes.get(coll, 0.0) + b)
                        stats.n_collectives[coll] = (
                            stats.n_collectives.get(coll, 0) + 1)
                        break
    return stats


# --------------------------------------------------------------------------- #
# Analytic HBM traffic model (documented, per device, per step)
# --------------------------------------------------------------------------- #

def param_bytes(cfg, quantized: bool) -> float:
    """Model weight bytes (global). Quantized: policy-covered GEMM weights at
    w_bits packed, embeddings/norms/router bf16. ``cfg.quant`` may be a
    single QuantPolicy or a qplan.QuantPlan — for a plan the catch-all GEMM
    policy (resolved for a representative dense tag) sets the bitwidth."""
    P = cfg.n_params()
    # representative GEMM class: the MLP projections hold the parameter
    # majority, so a mixed plan is billed at its catch-all rule rather than
    # an attention-specific one (approximation: all covered weights at one
    # bitwidth; attention falls back when a plan skips the MLP class)
    pol = cfg.quant.policy_for("mlp.w_up") or cfg.quant.policy_for("attn.wq")
    if not quantized or pol is None or pol.w_bits is None:
        return P * 2.0
    embed = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    covered = P - embed
    group = (32.0 / pol.group_size) if pol.group_size else 0.0
    return covered * (pol.w_bits + group) / 8.0 + embed * 2.0


def kv_cache_bytes(cfg, batch: int, seq: int) -> float:
    """Global decode-cache bytes, honoring window-bounded layers, recurrent
    states and the serve-time cache dtype (int8 cache: 1 B + scales)."""
    dt = getattr(cfg, "kv_cache_dtype", "")
    bpe = {"int8": 1.0 + 4.0 / cfg.hd, "int4": 0.5 + 4.0 / cfg.hd}.get(dt, 2.0)
    total = 0.0
    for lt in cfg.layer_types:
        if lt == "global":
            total += 2 * batch * seq * cfg.n_kv_heads * cfg.hd * bpe
        elif lt == "local":
            total += 2 * batch * min(seq, cfg.window) * cfg.n_kv_heads * cfg.hd * bpe
        elif lt == "recurrent":
            total += batch * (cfg.d_rnn or cfg.d_model) * (4 + cfg.conv_width) * 2
        elif lt == "rwkv":
            hd = cfg.rwkv_head_size
            total += batch * (cfg.d_model // hd) * hd * hd * 4 + 2 * batch * cfg.d_model * 2
    if cfg.is_encdec:
        total += 2 * cfg.n_layers * batch * cfg.encoder_seq * cfg.n_kv_heads * cfg.hd * 2
    return total


def hbm_traffic(cfg, shape, n_devices: int, *, quantized: bool,
                opt_bytes_per_param: float = 2.13) -> float:
    """Per-device HBM bytes moved per step (analytic, lower-bound-ish).

    train   : weights read fwd + read bwd + grad write (bf16) + optimizer
              moment read+write + activation save/restore traffic.
    prefill : weights read once + activations written once per layer.
    decode  : weights read once + full KV cache read + tiny writes.
    """
    B, S = shape.global_batch, shape.seq_len
    pb = param_bytes(cfg, quantized)
    D, L = cfg.d_model, cfg.n_layers
    if shape.kind == "train":
        act = B * S * D * L * 2 * 2.0         # save + reload one resid/layer (remat)
        traffic = pb * 3 + cfg.n_params() * (2 * opt_bytes_per_param) * 2 + act
    elif shape.kind == "prefill":
        act = B * S * D * L * 2 * 2.0
        traffic = pb + act
    else:  # decode
        traffic = pb + kv_cache_bytes(cfg, B, S) + B * D * L * 2 * 4.0
    return traffic / n_devices


# --------------------------------------------------------------------------- #
# Roofline assembly
# --------------------------------------------------------------------------- #

def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) for train; 2*N*D for
    prefill; 2*N_active per decoded token (D = tokens processed)."""
    n_active = cfg.n_active_params()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    return 2.0 * n_active * tokens


def roofline(stats: HloStats, cfg, shape, n_devices: int, *,
             quantized: bool) -> dict:
    # SPMD HLO is the per-device program: parsed flops/bytes are per device.
    comp = stats.dot_flops / PEAK_FLOPS
    memb = hbm_traffic(cfg, shape, n_devices, quantized=quantized)
    mem = memb / HBM_BW
    coll = stats.total_collective_bytes / ICI_BW
    terms = {"compute_s": comp, "memory_s": mem, "collective_s": coll}
    bound = max(terms, key=terms.get)
    step_time = max(comp, mem, coll)
    mf = model_flops(cfg, shape)
    hlo_flops_global = stats.dot_flops * n_devices
    return {
        **terms,
        "bound": bound.replace("_s", ""),
        "step_time_lower_bound_s": step_time,
        "hlo_dot_flops_global": hlo_flops_global,
        "model_flops": mf,
        "useful_flop_ratio": mf / max(hlo_flops_global, 1.0),
        "hbm_bytes_per_dev": memb,
        "collective_bytes_per_dev": stats.total_collective_bytes,
        "collective_breakdown": dict(stats.collective_bytes),
        "mfu_upper_bound": (mf / n_devices / PEAK_FLOPS) / max(step_time, 1e-12),
    }
