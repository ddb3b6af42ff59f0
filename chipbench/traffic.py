"""Request streams for a traffic mix, read from its data file.

A mix (``traffic/<mix>.json``) gives prompt and output lengths as clipped
lognormals, gaps between arrivals as a Gamma law of a stated coefficient of
variation (1 is Poisson), the load as a share of the slots, and the number
of requests per slot in one replay.  Arrivals are in decode steps, the
engine's virtual clock: the rate is

    rho = load * slots / mean output length    requests per decode step,

so the mean number of busy slots is ``load * slots`` whatever the speed of
the chip.

Every seed gets the same prompt lengths, output lengths and gaps, drawn
once from a fixed stream, and in the same order: replay ``i`` of every run
puts them in the order that ``i`` draws.  The seed draws the prompts'
tokens (and the weights).  So two seeds ask for the same work at the same
moments.  On the chip, letting the seed order the requests moved a chat
window's ``tok_s`` by 8-17% and its TTFT p90 by more than 100% between
seeds, against 0-2% between two runs of one seed: which request lands in
a burst decides the queue.
"""
from __future__ import annotations

import dataclasses
import json
from typing import List, Tuple

import numpy as np

from repro.runner.traces import Request

#: the fixed stream that draws a mix's sizes, the same for every seed
SIZES_STREAM = 7001


@dataclasses.dataclass(frozen=True)
class Lengths:
    median: float
    sigma: float
    min: int
    max: int

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        x = self.median * np.exp(self.sigma * rng.standard_normal(n))
        return np.clip(np.rint(x), self.min, self.max).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    prompt: Lengths
    output: Lengths
    gap_cv: float
    load: float
    requests_per_slot: int

    @classmethod
    def load_file(cls, path: str, name: str) -> "Mix":
        with open(path) as f:
            d = json.load(f)
        return cls(name=name, prompt=Lengths(**d["prompt"]),
                   output=Lengths(**d["output"]), gap_cv=float(d["gap_cv"]),
                   load=float(d["load"]),
                   requests_per_slot=int(d["requests_per_slot"]))

    def max_len(self) -> int:
        """Cache length that holds the longest prompt with the longest
        output (the last output token is never written back)."""
        return self.prompt.max + self.output.max

    def warm_prompt_lengths(self) -> List[int]:
        """Prompt lengths that reach every padded admission length a
        replay can reach: both ends of the range and each power of two in
        it (the engine pads prompts to powers of two)."""
        lo, hi = self.prompt.min, self.prompt.max
        pts = {lo, hi}
        p = 1
        while p <= hi:
            if p >= lo:
                pts.add(p)
            p *= 2
        return sorted(pts)


def sizes(mix: Mix, slots: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The replay's prompt lengths, output lengths and gaps (in decode
    steps), drawn once for every seed."""
    n = mix.requests_per_slot * slots
    rng = np.random.default_rng(SIZES_STREAM)
    prompts = mix.prompt.draw(rng, n)
    outputs = mix.output.draw(rng, n)
    rho = mix.load * slots / float(outputs.mean())
    shape = 1.0 / (mix.gap_cv ** 2)
    gaps = rng.gamma(shape, 1.0, n)
    # scaled so that this sample's mean gap is 1/rho: the load is exact
    return prompts, outputs, gaps / (gaps.mean() * rho)


def replay(mix: Mix, slots: int, vocab: int, seed: int,
           index: int) -> List[Request]:
    """Replay ``index`` of a run with ``seed``: the fixed sizes and gaps in
    the order that ``index`` draws, prompts of tokens that ``(seed, index)``
    draws."""
    prompts, outputs, gaps = sizes(mix, slots)
    order = np.random.default_rng([SIZES_STREAM, index])
    prompts = prompts[order.permutation(len(prompts))]
    outputs = outputs[order.permutation(len(outputs))]
    gaps = gaps[order.permutation(len(gaps))]
    rng = np.random.default_rng([seed, index])
    arrivals = np.floor(np.cumsum(gaps)).astype(np.int64)
    return [Request(rid=i, prompt=rng.integers(0, vocab, int(p), dtype=np.int32),
                    max_new=int(o), arrival_step=int(a))
            for i, (p, o, a) in enumerate(zip(prompts, outputs, arrivals))]


def warm_requests(mix: Mix, slots: int, vocab: int) -> List[Request]:
    """One trace that makes the engine admit every (rows, padded length)
    shape the mix can reach, then decode: for each warm prompt length and
    each power-of-two row count up to ``slots``, that many one-token
    requests arrive together at their own step; one last request asks for
    two tokens."""
    rng = np.random.default_rng(0)
    reqs: List[Request] = []
    step = 0
    for n in mix.warm_prompt_lengths():
        k = 1
        while True:
            for _ in range(min(k, slots)):
                reqs.append(Request(rid=len(reqs), max_new=1, arrival_step=step,
                                    prompt=rng.integers(0, vocab, n, dtype=np.int32)))
            step += 1
            if k >= slots:
                break
            k *= 2
    reqs.append(Request(rid=len(reqs), max_new=2, arrival_step=step,
                        prompt=rng.integers(0, vocab, mix.prompt.min,
                                            dtype=np.int32)))
    return reqs
