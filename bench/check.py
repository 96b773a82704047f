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
    The cell's packed weight ops have to run as the TPU kernel: dispatches
    on any other backend of any op are counted against a limit of 0, and
    each packed op's own ``pallas`` dispatches have to be at least one (the
    number compared is the least of them).

``gaps(..., control_dtype=...)`` also reads the gap of the token that the
control (the reference in a lower precision) ranks first at each position;
``bench/calibrate.py`` uses it to set the limit.
"""

from __future__ import annotations

import dataclasses

import numpy as np

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


def dispatch_checks(counts: dict, ops: list) -> dict:
    bad = sum(n for k, n in counts.items() if not k.endswith(":pallas"))
    least = min(counts.get(f"{op}:pallas", 0) for op in ops)
    return {"pallas_dispatches": {"value": least, "limit": 1,
                                  "at_least": True},
            "non_pallas_dispatches": {"value": bad, "limit": 0}}


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
    out = {"served": [], "control": []}
    for r in reqs:
        tokens, rows = _rows(r)
        lg = ref.logits(weights, config, tokens, rows, length=length,
                        n_rows=n_rows)
        best = lg.max(-1)
        served = lg[np.arange(len(rows)), np.asarray(r.out)]
        out["served"].append(best - served)
        if control_dtype is not None:
            lc = ref.logits(weights, config, tokens, rows, length=length,
                            n_rows=n_rows, act_dtype=control_dtype)
            pick = lc.argmax(-1)
            out["control"].append(best - lg[np.arange(len(rows)), pick])
    return out

