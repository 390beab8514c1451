"""The one traffic generator: turns a ``traffic/<name>.json`` file of
parameters and a seed into the inputs the program receives.

Two kinds of mix:

* ``lm_batches`` — training batches of ``batch`` × ``seq_len`` tokens plus
  their next-token labels, from an order-1 Markov chain over the vocabulary
  (``branching`` successors per token, a ``noise`` share of uniform tokens).
  Batch ``i`` of seed ``s`` depends on ``(s, i)`` alone. (A copy of the
  ``markov`` stream of ``src/repro/data/synthetic.py``, kept here so the
  benchmark's inputs do not move with the program.)
* ``open_loop`` — requests with prompt and output lengths and due times.
  The lengths and the gaps between arrivals are quantiles of the stated
  distributions, put in an order drawn from the traffic's own
  ``schedule_seed``: every run of a cell gets the same schedule, and the run's
  seed draws the token ids. Seeds change what is asked, not how much work
  a run holds or when it comes.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

SEED_SALT = 0x77324B


# ---------------------------------------------------------------------------
# training batches
# ---------------------------------------------------------------------------

def _seed_words(seed: int) -> list[int]:
    """Seeds beyond 32 bits, negative ones too, as SeedSequence words."""
    seed = int(seed)
    return [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, 1 if seed < 0 else 0]


class MarkovBatches:
    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.t, self.vocab, self.seed = traffic, vocab, seed
        g = np.random.default_rng(np.random.SeedSequence(_seed_words(seed)))
        self.next = g.integers(0, vocab, size=(vocab, traffic["branching"]))

    def batch(self, i: int) -> dict:
        B, S = self.t["batch"], self.t["seq_len"]
        rng = np.random.default_rng(np.random.SeedSequence(
            _seed_words(self.seed) + [i, SEED_SALT]))
        toks = np.empty((B, S + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, size=B)
        choices = rng.integers(0, self.t["branching"], size=(B, S))
        noise = rng.random((B, S)) < self.t["noise"]
        rand_tok = rng.integers(0, self.vocab, size=(B, S))
        for s in range(S):
            step_tok = self.next[toks[:, s], choices[:, s]]
            toks[:, s + 1] = np.where(noise[:, s], rand_tok[:, s], step_tok)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


# ---------------------------------------------------------------------------
# open-loop requests
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Arrival:
    uid: int
    due_s: float          # seconds after the window opens
    prompt: list[int]
    max_new: int


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of a lognormal with the given
    median and sigma, rounded and clipped to [min, max]."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def open_loop(traffic: dict, vocab: int, seed: int, seconds: float) -> list[Arrival]:
    """Requests due in ``[0, seconds)`` at ``traffic["rate_per_s"]``."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(math.floor(rate * seconds)))
    order = np.random.default_rng(np.random.SeedSequence(
        _seed_words(traffic["schedule_seed"]) + [SEED_SALT]))
    prompts = order.permutation(_lognormal_quantiles(traffic["prompt"], n))
    outputs = order.permutation(_lognormal_quantiles(traffic["output"], n))
    # exponential gaps at their quantiles, with mean exactly 1 / rate
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = order.permutation(gaps / gaps.mean() / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])]) if n > 1 else np.zeros(1)
    rng = np.random.default_rng(np.random.SeedSequence(_seed_words(seed) + [SEED_SALT]))
    return [Arrival(uid=i, due_s=float(due[i]),
                    prompt=[int(t) for t in rng.integers(0, vocab, int(prompts[i]))],
                    max_new=int(outputs[i]))
            for i in range(n)]


def warmup_requests(vocab: int, slots: int) -> list[Arrival]:
    """Requests that fill every slot through a few prefill and decode ticks
    before the window (the tick programs compile once per shape)."""
    rng = np.random.default_rng(SEED_SALT)
    return [Arrival(uid=-(i + 1), due_s=0.0,
                    prompt=[int(t) for t in rng.integers(0, vocab, 40)], max_new=4)
            for i in range(slots)]
