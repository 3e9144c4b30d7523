"""The one generator of traffic. A traffic mix is a data file
(`chipbench/traffic/<name>.json`) of parameters that this module reads; a new
mix is a new file, never new code. The kinds a mix can name are files too,
found by name: `lengths/<dist>.py` (`pool(dist, n)`), `arrivals/<kind>.py`
(`gaps(arrivals, n)`, None where everything is due at once), `rows/<shape>.py`
(`rows(traffic, vocab, seed)`).

Everything comes from the seed, and every seed gets THE SAME multiset of sizes
and gaps in another order: lengths and inter-arrival gaps are the fixed
quantiles of their distribution (a pool of `pool` values), shuffled by the seed,
pool after pool. Runs with different seeds then do the same work, and differ
only in how it is interleaved.

`"kind": "requests"` (serving):
    prompt_len, output_len   {"dist": <lengths/>, ...its parameters}
    pool                     values a pool (default 256)
    arrivals                 {"kind": <arrivals/>, ...its parameters}
    ramp_s                   seconds of this traffic before the window opens
`"kind": "batches"` (training):
    shape                    <rows/>
    seq_len, examples        rows are made once, then cycled in seeded order
"""

from __future__ import annotations

import os

import numpy as np

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def length_pool(dist: dict, n: int) -> np.ndarray:
    return harness.load_module("lengths", dist["dist"], ROOT).pool(dist, n)


class RequestStream:
    """Request i of a serving mix: `(due_s, prompt_ids, output_len)`, the same
    for the same seed whenever it is asked for."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        if traffic.get("kind") != "requests":
            raise ValueError("not a serving traffic file")
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)
        self.pool = int(traffic.get("pool", 256))
        self._prompt_pool = length_pool(traffic["prompt_len"], self.pool)
        self._output_pool = length_pool(traffic["output_len"], self.pool)
        arrivals = traffic["arrivals"]
        self._gap_pool = harness.load_module("arrivals", arrivals["kind"], ROOT).gaps(arrivals, self.pool)
        self.backlog = self._gap_pool is None
        self._rng = np.random.default_rng([self.seed, 1])
        self._sizes: list = []  # (due_s, prompt_len, output_len)
        self._clock = 0.0

    @property
    def mean_output_len(self) -> float:
        return float(self._output_pool.mean())

    @property
    def max_output_len(self) -> int:
        return int(self._output_pool.max())

    def _grow(self) -> None:
        prompts = self._rng.permutation(self._prompt_pool)
        outputs = self._rng.permutation(self._output_pool)
        gaps = None if self.backlog else self._rng.permutation(self._gap_pool)
        for j in range(self.pool):
            if gaps is not None:
                self._clock += float(gaps[j])
            self._sizes.append((self._clock, int(prompts[j]), int(outputs[j])))

    def sizes(self, i: int) -> tuple:
        while len(self._sizes) <= i:
            self._grow()
        return self._sizes[i]

    def prompt(self, i: int) -> np.ndarray:
        _due, prompt_len, _out = self.sizes(i)
        rng = np.random.default_rng([self.seed, 3, i])
        return rng.integers(1, self.vocab_size, prompt_len).astype(np.int32)

    def request(self, i: int) -> tuple:
        due, _prompt_len, out = self.sizes(i)
        return due, self.prompt(i), out


def training_rows(traffic: dict, vocab_size: int, seed: int) -> dict:
    """Columns `[examples, ...]` of a training mix."""
    if traffic.get("kind") != "batches":
        raise ValueError("not a training traffic file")
    return harness.load_module("rows", traffic["shape"], ROOT).rows(traffic, vocab_size, seed)
