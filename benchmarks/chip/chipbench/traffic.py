"""The one traffic generator: requests drawn from a traffic file and a seed.

A traffic file gives the lengths as distributions (``{"log_uniform":
[lo, hi]}``).  Every seed gets the same spread of sizes: request ``i``
takes the quantile ``frac(u0 + i * phi)`` of each distribution, a
golden-ratio sequence whose every prefix covers ``[0, 1)`` evenly, and
only the offsets ``u0`` and the token ids come from the seed.  So two
seeds differ in the order of the sizes and in the tokens, not in how
much work a window holds.

Seeds are whole numbers of any size: they go through numpy's
``SeedSequence``, never straight into a 32-bit key.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Iterator, List

import numpy as np

#: golden-ratio increments of the two low-discrepancy sequences
PHI_PROMPT = (math.sqrt(5.0) - 1.0) / 2.0
PHI_OUTPUT = math.sqrt(2.0) - 1.0


@dataclasses.dataclass(frozen=True)
class Request:
    client: int
    index: int                  # the order in which it was drawn
    uid: str
    prompt: np.ndarray          # (n,) int32 token ids
    max_new: int


def seed_sequence(seed: int, *tag: int) -> np.random.SeedSequence:
    """The seed as numpy takes it, with ``tag`` keeping streams apart."""
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    return np.random.SeedSequence([seed, *tag])


def jax_seed(seed: int, tag: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey`` drawn from the seed:
    ``PRNGKey`` keeps only the low 32 bits of a larger integer."""
    return int(seed_sequence(seed, tag).generate_state(1)[0] & 0x7FFFFFFF)


def quantile(dist: Dict[str, Any], u: float) -> int:
    """Size at quantile ``u`` of a length distribution."""
    lo, hi = size_bounds(dist)
    return int(round(lo * (hi / lo) ** u))


def size_bounds(dist: Dict[str, Any]) -> tuple:
    if "log_uniform" not in dist:
        raise ValueError(f"unknown length distribution {dist!r}")
    return tuple(dist["log_uniform"])


class ClosedLoop:
    """``clients`` independent request streams.  A client sends its next
    request only when the previous one has completed."""

    def __init__(self, traffic: Dict[str, Any], vocab: int, seed: int):
        self.traffic = traffic
        self.vocab = int(vocab)
        self.clients = int(traffic["clients"])
        ss = seed_sequence(seed, 1)
        u = np.random.default_rng(ss).random(2)
        self._u0 = (float(u[0]), float(u[1]))
        self._seed = seed
        self._count = 0

    def sizes(self, i: int):
        """(prompt tokens, output tokens) of the ``i``-th request drawn."""
        up = (self._u0[0] + i * PHI_PROMPT) % 1.0
        uo = (self._u0[1] + i * PHI_OUTPUT) % 1.0
        return (quantile(self.traffic["prompt_tokens"], up),
                quantile(self.traffic["output_tokens"], uo))

    def next(self, client: int) -> Request:
        """The next request overall, handed to ``client``."""
        i = self._count
        self._count += 1
        n_prompt, n_out = self.sizes(i)
        rng = np.random.default_rng(seed_sequence(self._seed, 2, i))
        prompt = rng.integers(0, self.vocab, size=n_prompt, dtype=np.int32)
        return Request(client=client, index=i, uid=f"r{i}",
                       prompt=prompt, max_new=n_out)

    def first(self) -> List[Request]:
        return [self.next(c) for c in range(self.clients)]


def calibration_tokens(traffic: Dict[str, Any], vocab: int,
                       seed: int) -> np.ndarray:
    """The token batch that calibrates the ADC and activation ranges."""
    b, s = traffic["calibration_tokens"]
    rng = np.random.default_rng(seed_sequence(seed, 3))
    return rng.integers(0, vocab, size=(b, s), dtype=np.int32)


def warm_shapes(traffic: Dict[str, Any]) -> Iterator[tuple]:
    """Every (prompt bucket, admission-group size) a closed loop of this
    traffic can prefill: groups are powers of two up to the slots."""
    lo, hi = size_bounds(traffic["prompt_tokens"])
    buckets = sorted(traffic["buckets"])
    used = [b for i, b in enumerate(buckets)
            if b >= lo and (i == 0 or buckets[i - 1] < hi)]
    slots = int(traffic["slots"])
    most = min(slots, int(traffic["clients"]))
    groups, g = [], 1
    while True:
        groups.append(min(g, slots))
        if g >= most:
            break
        g *= 2
    for b in used:
        for g in sorted(set(groups)):
            yield b, g
