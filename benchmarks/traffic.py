"""One general traffic generator, driven by a data file.

A traffic mix is ``benchmarks/traffic/<name>.json``: lengths, rates, bursts
and sharing as parameters.  Nothing here names a mix or a cell; a later PR
adds a mix by adding a file.

**Every seed offers the same load.**  Lengths and gaps are not sampled: they
are read off the inverse CDF at stratified points ``(i + 0.5) / n``, in
*segments* of equal duration that all hold the same values, and the seed only permutes each list inside
its segment (prompt lengths, output lengths and gaps independently) and
makes the token ids.  So the multiset of prompt lengths, of output lengths
and of gaps in segment k is the same for every seed, and so are the offered
tokens and the mean rate of every ``segment_seconds`` of the run; what
differs is the order, which is what keeps a shuffled exponential sample as
bursty as Poisson arrivals are.  ``max_new`` is the drawn length and EOS is
off, so random weights cannot shorten a request.

Kinds of mix (``"kind"``):

- ``train``: no requests; ``train_corpus`` gives the token stream that the
  program's loader packs into rows.
- ``serve`` with ``arrivals.process`` ``open_loop`` (due times on a
  schedule, rate fixed in the file) or ``backlog`` (a queue kept
  ``arrivals.depth`` requests deep; the first ``arrivals.stagger`` requests
  have their output lengths cut to stratified fractions so that slots retire
  spread out from the first second and not in waves).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    """The mix ``name``.  A file that names a ``base`` is that mix with the
    file's own keys laid over it: one job offered to another cell (a pair of
    configuration and traffic may appear once in ``BENCHMARK.json``) is
    then one set of parameters, not two kept in step by hand."""
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if "base" in mix:
        mix = {**load(mix.pop("base")), **mix}
    if mix.get("kind") not in ("train", "serve"):
        raise ValueError(f"traffic {name!r}: kind must be train or serve")
    return mix


def inverse_cdf(dist: dict, u: np.ndarray) -> np.ndarray:
    """Values of ``dist`` at the probabilities ``u`` in (0, 1)."""
    kind = dist["dist"]
    if kind == "constant":
        x = np.full(u.shape, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * u
    elif kind == "exponential":
        x = -float(dist["mean"]) * np.log1p(-u)
    elif kind == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif kind == "gamma":
        # bursty gaps (BurstGPT): shape < 1 is burstier than Poisson
        from scipy import stats
        shape = float(dist["shape"])
        x = stats.gamma.ppf(u, shape, scale=float(dist["mean"]) / shape)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist and kind != "uniform":
        x = np.maximum(x, dist["min"])
    if "max" in dist and kind != "uniform":
        x = np.minimum(x, dist["max"])
    return x


def stratified(dist: dict, n: int, phase: float) -> np.ndarray:
    """n values at the stratified points (i + phase) / n."""
    return inverse_cdf(dist, (np.arange(n) + phase) / n)


@dataclass
class Request:
    index: int
    due: float            # seconds after the first arrival; 0 for a backlog
    prompt: np.ndarray    # int32 token ids
    max_new: int
    greedy: bool


class Requests:
    """The request stream of a ``serve`` mix, segment by segment."""

    def __init__(self, mix: dict, seed: int, vocab_size: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab_size)
        arr = mix["arrivals"]
        self.process = arr["process"]
        if self.process == "open_loop":
            self.seg_seconds = float(arr["segment_seconds"])
            self.seg_n = int(round(arr["rate_per_s"] * self.seg_seconds))
        elif self.process == "backlog":
            self.seg_seconds = 0.0
            self.seg_n = int(arr["segment_requests"])
        else:
            raise ValueError(f"unknown arrival process {self.process!r}")
        if self.seg_n < 1:
            raise ValueError("a segment holds no request")

    def segment_lengths(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        """Segment k's prompt and output lengths before the seed permutes
        them: the same for every seed."""
        del k   # every segment reads the same strata: the same load
        prompts = np.rint(stratified(self.mix["prompt_len"], self.seg_n,
                                     0.5)).astype(np.int64)
        outs = np.rint(stratified(self.mix["output_len"], self.seg_n,
                                  0.5)).astype(np.int64)
        return prompts, outs

    def segment_gaps(self, k: int) -> np.ndarray:
        """Segment k's gaps, scaled so they span ``segment_seconds``."""
        if self.process != "open_loop":
            return np.zeros(self.seg_n)
        gaps = stratified(self.mix["arrivals"]["gaps"], self.seg_n, 0.5)
        return gaps * (self.seg_seconds / gaps.sum())

    def segment(self, k: int) -> list[Request]:
        rng = np.random.default_rng([self.seed, k])
        prompts, outs = self.segment_lengths(k)
        gaps = self.segment_gaps(k)
        # greedy requests are fixed by output-length rank before the
        # shuffle (the longest of each segment among them), so every seed
        # has the same number of them: the output check reads only those
        every = int(self.mix.get("greedy_every", 0))
        greedy = np.zeros(self.seg_n, bool)
        if every:
            greedy[np.argsort(-outs, kind="stable")[::every]] = True
        order = rng.permutation(self.seg_n)
        outs, greedy = outs[order], greedy[order]
        prompts = rng.permutation(prompts)
        gaps = rng.permutation(gaps)
        stagger = int(self.mix["arrivals"].get("stagger", 0))
        if k * self.seg_n < stagger:
            # the first requests of a backlog fill the slots together: cut
            # their outputs to stratified fractions so they retire apart
            n = min(stagger - k * self.seg_n, self.seg_n)
            frac = (np.arange(n) + 0.5) / n
            outs[:n] = np.maximum(
                np.rint(outs[:n] * rng.permutation(frac)), 2)
        due = k * self.seg_seconds + np.cumsum(gaps) - gaps
        shared = self.mix.get("shared_prefix")
        out = []
        for i in range(self.seg_n):
            ids = rng.integers(0, self.vocab, int(prompts[i]),
                               dtype=np.int64).astype(np.int32)
            if shared:
                # sessions: a group's prompts start with the same tokens
                g = int(rng.integers(0, int(shared["groups"])))
                pre = np.random.default_rng([self.seed, 1 << 20, g]).integers(
                    0, self.vocab, int(shared["length"]),
                    dtype=np.int64).astype(np.int32)
                m = min(len(pre), len(ids) - 1)
                ids[:m] = pre[:m]
            out.append(Request(k * self.seg_n + i, float(due[i]), ids,
                               int(outs[i]), bool(greedy[i])))
        return out

    def offered(self, k: int) -> dict:
        """What segment k offers, whatever the seed."""
        prompts, outs = self.segment_lengths(k)
        return {"requests": self.seg_n, "prompt_tokens": int(prompts.sum()),
                "output_tokens": int(outs.sum()),
                "span_s": float(self.segment_gaps(k).sum())}


def train_corpus(seed: int, vocab_size: int, n_tokens: int) -> np.ndarray:
    """The token stream a ``train`` mix is packed from: ids uniform over
    the vocabulary, so every row differs and the 103k head and table do
    their full work (documents packed end to end have no structure the
    model's cost depends on)."""
    rng = np.random.default_rng([int(seed), 7])
    return rng.integers(0, vocab_size, n_tokens, dtype=np.int64).astype(
        np.int32)
