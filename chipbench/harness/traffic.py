"""The one traffic generator: every mix is a data file of parameters.

``LengthDist`` is a copy of ``repro.serving.workload.LengthDist`` (a clamped
lognormal whose unclamped mean is ``mean``), kept here so that the yardstick
cannot move with the program.

Run seeds change the order of the work, not its amount: the lengths of a mix
are drawn once from the file's own ``pool_seed``, each wave takes the next
slice of them, and a run's seed deals a wave's lengths to its slots and draws
the prompt tokens.
"""

from __future__ import annotations

import dataclasses
import math
import random

import numpy as np


@dataclasses.dataclass(frozen=True)
class LengthDist:
    mean: float
    sigma: float = 0.6
    lo: int = 1
    hi: int = 8192

    def sample(self, rng: random.Random) -> int:
        if self.sigma == 0:
            raw = self.mean
        else:
            mu = math.log(self.mean) - 0.5 * self.sigma * self.sigma
            raw = rng.lognormvariate(mu, self.sigma)
        return max(self.lo, min(self.hi, round(raw)))


@dataclasses.dataclass(frozen=True)
class Wave:
    """One static batch: every slot's prompt and how many tokens it wants."""
    prompts: np.ndarray          # (B, P) int32
    outputs: list[int]           # per slot, >= 1


class ServeTraffic:
    """Closed-loop clients served in static waves (see the mix's file)."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.B = int(mix["clients"])
        self.P = int(mix["prompt_tokens"])
        self.dist = LengthDist(**mix["output_tokens"])
        pool_rng = random.Random(int(mix["pool_seed"]))
        self.pool = [self.dist.sample(pool_rng) for _ in range(int(mix["pool_requests"]))]
        self.vocab = vocab
        self.seed = seed

    @property
    def max_output(self) -> int:
        return self.dist.hi

    def wave(self, i: int) -> Wave:
        """Wave ``i`` asks for the same set of lengths under every seed; the
        seed deals them to the slots and draws the prompts."""
        n = len(self.pool)
        outs = [self.pool[(i * self.B + j) % n] for j in range(self.B)]
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, (self.seed >> 32) & 0xFFFFFFFF, i])
        outs = [outs[j] for j in rng.permutation(self.B)]
        prompts = rng.integers(0, self.vocab, (self.B, self.P), dtype=np.int32)
        return Wave(prompts, outs)


# -- training: the corpus the job stages in ------------------------------------
def token_block(seed: int, start: int, count: int, vocab: int) -> np.ndarray:
    """Tokens at corpus positions [start, start + count): a copy of the
    program's counter-mode hash (``repro.data.synthetic.token_block``), so the
    reference reads the corpus without the program's loader."""
    idx = np.arange(start, start + count, dtype=np.uint64)
    mix = (seed * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    with np.errstate(over="ignore"):
        z = idx + np.uint64(mix)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return (z % np.uint64(vocab)).astype(np.int32)


def train_batch(seed: int, step: int, batch: int, seq: int, vocab: int) -> dict:
    """Rows ``step * batch ...`` of the corpus as next-token pairs."""
    toks = token_block(seed, step * batch * (seq + 1), batch * (seq + 1), vocab)
    toks = toks.reshape(batch, seq + 1)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
