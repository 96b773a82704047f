"""The comparison that decides a run's ``correct``.

Each check is a number beside its limit (``harness.run`` adds
``compiles_in_window``, limit 0, and ``sampled_tokens``, at least 1):

``logit_gap``
    Once the window has closed and the program's state is freed, a sample
    of the finished requests, drawn from the seed and holding the longest
    one, is run through the configuration's plain reference, once over each
    prompt followed by its served tokens. At every served position the gap
    by which the served token's reference logit lies below the reference's
    best is read; the number compared is the widest gap. Greedy serving of
    a correct program only loses a near-tie, so the gap stays small; a wrong
    layer, token or cache row picks tokens the reference ranks far down.
``non_pallas_dispatches`` / ``pallas_dispatches``
    The cell's packed weight op has to run as the TPU kernel: dispatches on
    any other backend of any op are counted against a limit of 0, and the
    packed op's own ``pallas`` dispatches have to be at least one.

``gaps(..., control_dtype=...)`` also reads the gap of the token that the
control (the reference in a lower precision) ranks first at each position;
``bench/calibrate.py`` uses it to set the limit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

import numpy as np

CONFIGS = Path(__file__).resolve().parent / "configs"
SAMPLE_TOKENS = 512        # served tokens the sample reaches at least,
SAMPLE_REQUESTS = 16       # unless it holds this many requests


def dispatch_counts(counters: dict) -> dict:
    """``kernel_dispatch_total`` counters summed as {"op:backend": n}."""
    out: dict[str, int] = {}
    for key, v in counters.items():
        if not key.startswith("kernel_dispatch_total{"):
            continue
        labels = dict(p.split("=", 1)
                      for p in key[key.index("{") + 1:-1].split(","))
        k = f"{labels['op']}:{labels['backend']}"
        out[k] = out.get(k, 0) + int(v)
    return out


def dispatch_checks(counts: dict, op: str) -> dict:
    bad = sum(n for k, n in counts.items() if not k.endswith(":pallas"))
    return {"pallas_dispatches": {"value": counts.get(f"{op}:pallas", 0),
                                  "limit": 1, "at_least": True},
            "non_pallas_dispatches": {"value": bad, "limit": 0}}


def load_reference(config: dict):
    """The configuration's plain reference module (``reference`` key)."""
    path = CONFIGS / config["reference"]
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Served:
    uid: int
    prompt: np.ndarray            # (P,) int32
    out: list                     # served token ids


def sample(finished: list, seed: int, min_tokens: int = SAMPLE_TOKENS):
    """The longest finished request (prompt plus served tokens), then
    others in an order drawn from the seed until ``min_tokens`` served
    tokens or SAMPLE_REQUESTS requests are reached."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.prompt) + len(r.out), r.uid))
    rest = sorted((r for r in finished if r is not longest),
                  key=lambda r: r.uid)
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    chosen, n = [longest], len(longest.out)
    for i in order:
        if n >= min_tokens or len(chosen) >= SAMPLE_REQUESTS:
            break
        chosen.append(rest[i])
        n += len(rest[i].out)
    return chosen


def _rows(r: Served):
    """(tokens fed, positions whose logits predict the served tokens)."""
    P, n = len(r.prompt), len(r.out)
    tokens = np.concatenate([np.asarray(r.prompt, np.int32),
                             np.asarray(r.out[:n - 1], np.int32)])
    return tokens, np.arange(P - 1, P - 1 + n)


def gaps(ref, weights, config: dict, reqs: list, *, length: int,
         n_rows: int, control_dtype=None) -> dict:
    """Per sampled request, the gap at every served position: of the served
    token and, with ``control_dtype``, of the control's first choice."""
    sizes, quant = reference_args(config)
    out = {"served": [], "control": []}
    for r in reqs:
        tokens, rows = _rows(r)
        lg = ref.logits(weights, sizes, quant, tokens, rows, length=length,
                        n_rows=n_rows)
        best = lg.max(-1)
        served = lg[np.arange(len(rows)), np.asarray(r.out)]
        out["served"].append(best - served)
        if control_dtype is not None:
            lc = ref.logits(weights, sizes, quant, tokens, rows,
                            length=length, n_rows=n_rows,
                            act_dtype=control_dtype)
            pick = lc.argmax(-1)
            out["control"].append(best - lg[np.arange(len(rows)), pick])
    return out


def reference_args(config: dict) -> tuple[dict, dict]:
    """The sizes and quantisation statement the reference reads, as flat
    dicts of numbers."""
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "rope_theta", "rms_norm_eps")
    sizes = {k: config[k] for k in keys}
    q = config["quant"]
    quant = {"weight_bits": q["weight_bits"], "act_bits": q["act_bits"],
             "kv_cache_levels": q["kv_cache_levels"]}
    return sizes, quant
