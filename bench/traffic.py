"""Traffic generator: one general reader of the mix files in ``bench/traffic``.

A mix file states the loop and the request shapes; nothing else is code.

``loop``
    ``"batch"``: serve.py's fixed-batch loop takes ``slots`` requests at a
    time, all of one prompt length and one output length.
``slots``
    The server's decode batch (serve.py ``--batch``) sized for this mix.
``prompt`` / ``output``
    Length distributions ``{"dist": "uniform", "min": a, "max": b}``; a
    batch mix gives each one length (``min == max``).

Every seed gets the same work in another order: lengths are drawn as the
stratified quantiles ``(i + 0.5) / BLOCK`` of their distributions, a block
of BLOCK requests at a time, and the seed permutes each block. Token ids
come from the seed and the request's index.
"""

from __future__ import annotations

import dataclasses

import numpy as np

BLOCK = 256        # requests per stratified block
LOOPS = ("batch",)


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""
    uid: int
    prompt: np.ndarray            # (P,) int32
    max_new: int


def _quantiles(spec: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] != "uniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(lo + np.floor(u * (hi - lo + 1)), lo, hi).astype(np.int64)


def _stratified(rng: np.random.Generator, n: int) -> np.ndarray:
    """n values in (0, 1): whole blocks of the BLOCK stratified quantiles,
    each block in its own random order."""
    u = (np.arange(BLOCK) + 0.5) / BLOCK
    blocks = [rng.permutation(u) for _ in range(-(-n // BLOCK))]
    return np.concatenate(blocks)[:n]


class Traffic:
    """The requests of one mix for one seed, generated on demand."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        if mix["loop"] not in LOOPS:
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self._lens = np.zeros((0, 2), np.int64)

    def _grow(self, n: int) -> None:
        """Plan requests up to index n, a whole block at a time."""
        while len(self._lens) < n:
            rng = np.random.default_rng([self.seed, 2, len(self._lens) // BLOCK])
            p = _quantiles(self.mix["prompt"], _stratified(rng, BLOCK))
            o = _quantiles(self.mix["output"], _stratified(rng, BLOCK))
            self._lens = np.concatenate([self._lens, np.stack([p, o], 1)])

    def request(self, i: int) -> Planned:
        """The i-th request of the mix (uid i)."""
        self._grow(i + 1)
        p_len, o_len = (int(x) for x in self._lens[i])
        rng = np.random.default_rng([self.seed, 3, i])
        prompt = rng.integers(0, self.vocab, p_len, dtype=np.int32)
        return Planned(uid=i, prompt=prompt, max_new=o_len)


def max_lengths(mix: dict) -> tuple[int, int]:
    """(longest prompt, longest output) the mix can send: the server sizes
    its per-request context from them."""
    return int(mix["prompt"]["max"]), int(mix["output"]["max"])
