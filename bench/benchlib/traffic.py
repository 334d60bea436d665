"""One general traffic generator, driven by a mix's data file.

Every seed gets the same multiset of prompt lengths and output budgets
(evenly spaced quantiles of the mix's distributions) in another order,
and its own token ids: runs with different seeds do the same amount of
work, so their spread is the system's, not the draw's.
"""
from __future__ import annotations

import math
import statistics
from typing import Iterator, Tuple

import numpy as np

# ids 0..2 are pad / bos / eos in the program's vocabulary: prompts avoid them
FIRST_ID = 3


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles, (i + 0.5) / n, of a length
    distribution: ``fixed`` (value), ``uniform`` (min, max: integers,
    inclusive) or ``lognormal`` (median, sigma, clipped to [min, max])."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "fixed":
        vals = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        lo, hi = spec["min"], spec["max"]
        vals = lo + q * (hi - lo + 1) - 0.5
    elif dist == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(x) for x in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
        vals = np.clip(vals, spec["min"], spec["max"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.rint(vals).astype(np.int64)


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from any whole number, however large."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


class GroupStream:
    """Endless GRPO groups: ``(group_id, prompt, max_new_tokens)``.

    The mix's ``pool`` lengths are drawn as quantiles once; each pass over
    the pool shuffles them again with the seed.  Prompts are unique per
    group (random ids), so no two groups share a prefix."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.vocab = vocab
        self.rng = np.random.default_rng(seed_words(seed))
        n = int(mix["pool"])
        self.prompt_lens = quantiles(mix["prompt_len"], n)
        self.new_tokens = quantiles(mix["new_tokens"], n)
        self._next = 0
        self._order = np.arange(0)

    def __iter__(self) -> Iterator[Tuple[int, np.ndarray, int]]:
        return self

    def __next__(self) -> Tuple[int, np.ndarray, int]:
        i = self._next % len(self.prompt_lens)
        if i == 0:
            self._order = self.rng.permutation(len(self.prompt_lens))
        j = self._order[i]
        gid = self._next
        self._next += 1
        prompt = self.rng.integers(FIRST_ID, self.vocab,
                                   int(self.prompt_lens[j])).astype(np.int32)
        return gid, prompt, int(self.new_tokens[j])


def replicated_prompts(stream: GroupStream, group_size: int):
    """``(prompt_id, tokens)`` with each prompt repeated ``group_size`` times,
    as the program's producers take them."""
    for gid, prompt, _ in stream:
        for _ in range(group_size):
            yield gid, prompt


def p90(values) -> float:
    """The 90th percentile (nearest rank); inf counts as infinitely late."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[min(len(v) - 1, math.ceil(0.9 * len(v)) - 1)])
