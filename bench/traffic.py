"""The one traffic generator: reads a mix's parameters from its data file.

A mix is a JSON file under ``bench/traffic/``.  Every random draw is
*stratified*: within each block of ``block`` draws the values are the
distribution's quantiles at (j + 0.5) / block, in an order shuffled by the
seed.  So every seed sends the same multiset of sizes, gaps, turns and
modes in every block, only in another order, and two seeds differ in work
far less than two independent samples would.  Token contents are plain
seeded random ids.

Keys of a mix (all lengths in tokens, times in seconds):

  arrival        {"kind": "poisson", "rate_per_s": r}  sessions per second
                 {"kind": "backlog", "waiting_per_slot": k}  keep k * B
                 requests waiting (a saturated offline batch)
  lead_in_s      traffic before the measured window opens
  block          draws per stratified block
  prompt         length law of a first turn's own text (after any shared
                 prefix) and of each later user message:
                 {"median", "sigma", "min", "max"} of a clipped lognormal
  output         the same for max_new_tokens
  shared_prefix  optional {"count", "tokens", "zipf_s"}: each session opens
                 with one of ``count`` prefixes, by Zipf popularity
  turns          optional {"min", "max", "gap_mean_s"}: turns per session,
                 uniform; turn k+1 is due an exponential gap after turn k
                 and its prompt is turn k's prompt, a seeded reply of turn
                 k's output length, and a new user message
  strict_share   share of sessions opened in STRICT mode
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, Iterator, List, Optional

import numpy as np

_STREAMS = {"gap": 1, "prompt": 2, "output": 3, "turns": 4, "turn_gap": 5,
            "prefix": 6, "mode": 7, "tokens": 8, "prefix_tokens": 9}


@dataclass
class Turn:
    """One request: due ``due_s`` seconds after the traffic starts."""
    session: int
    index: int                 # turn number within its session, from 0
    due_s: float
    prompt: List[int]
    max_new_tokens: int
    strict: bool
    shared_prefix: int = -1    # which shared prefix it opens with (-1: none)


def _rng(seed: int, stream: str, block: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), _STREAMS[stream], int(block)]))


def _quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


class Stratified:
    """Draws of one law, block by block: each block holds the law's
    quantiles at (j + 0.5) / block, shuffled by (seed, stream, block)."""

    def __init__(self, seed: int, stream: str, block: int, quantile) -> None:
        self.seed, self.stream, self.block = seed, stream, block
        self.values = [quantile(u) for u in _quantiles(block)]
        self._buf: List = []
        self._n = 0

    def __call__(self):
        if not self._buf:
            order = _rng(self.seed, self.stream, self._n).permutation(
                self.block)
            self._buf = [self.values[i] for i in order][::-1]
            self._n += 1
        return self._buf.pop()


def lognormal_quantile(law: Dict) -> callable:
    nd = NormalDist()
    mu, sigma = math.log(law["median"]), float(law["sigma"])
    lo, hi = int(law["min"]), int(law["max"])
    return lambda u: int(min(max(round(math.exp(mu + sigma * nd.inv_cdf(u))),
                                 lo), hi))


def exponential_quantile(mean: float) -> callable:
    return lambda u: -mean * math.log(1.0 - u)


def uniform_int_quantile(lo: int, hi: int) -> callable:
    n = hi - lo + 1
    return lambda u: lo + min(int(u * n), n - 1)


def zipf_quantile(count: int, s: float) -> callable:
    w = np.array([1.0 / (k + 1) ** s for k in range(count)])
    cdf = np.cumsum(w / w.sum())
    return lambda u: int(min(np.searchsorted(cdf, u, side="right"),
                             count - 1))


def bernoulli_quantile(p: float) -> callable:
    # the lowest quantiles are the hits: a block of 4 at p = 0.25 holds one
    return lambda u: bool(u < p)


class Generator:
    """Turns of a mix in due order, for one seed, one vocabulary and one
    per-request token cap (prompt + output)."""

    def __init__(self, mix: Dict, seed: int, vocab: int, cap: int) -> None:
        self.mix, self.seed, self.vocab, self.cap = mix, int(seed), vocab, cap
        b = int(mix.get("block", 16))
        self.gap = None
        arrival = mix["arrival"]
        if arrival["kind"] == "poisson":
            self.gap = Stratified(seed, "gap", b, exponential_quantile(
                1.0 / float(arrival["rate_per_s"])))
        self.prompt_len = Stratified(seed, "prompt", b,
                                     lognormal_quantile(mix["prompt"]))
        self.output_len = Stratified(seed, "output", b,
                                     lognormal_quantile(mix["output"]))
        turns = mix.get("turns") or {"min": 1, "max": 1, "gap_mean_s": 0.0}
        self.n_turns = Stratified(seed, "turns", b, uniform_int_quantile(
            int(turns["min"]), int(turns["max"])))
        self.turn_gap = Stratified(seed, "turn_gap", b, exponential_quantile(
            float(turns["gap_mean_s"])))
        self.strict = Stratified(seed, "mode", b, bernoulli_quantile(
            float(mix.get("strict_share", 0.0))))
        sp = mix.get("shared_prefix")
        self.prefixes: List[List[int]] = []
        if sp:
            self.prefix_pick = Stratified(seed, "prefix", b, zipf_quantile(
                int(sp["count"]), float(sp["zipf_s"])))
            rng = _rng(seed, "prefix_tokens", 0)
            self.prefixes = [rng.integers(1, vocab, int(sp["tokens"])).tolist()
                             for _ in range(int(sp["count"]))]
        self._session = 0

    def _tokens(self, session: int, turn: int, n: int,
                part: int) -> List[int]:
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed, _STREAMS["tokens"], session, turn, part]))
        return rng.integers(1, self.vocab, n).tolist()

    def session(self, start_s: float) -> List[Turn]:
        """The turns of the next session, whose first turn is due at
        ``start_s``.  A session stops before a turn that would pass the
        per-request cap."""
        sid = self._session
        self._session += 1
        n_turns = self.n_turns()
        strict = self.strict()
        pick = self.prefix_pick() if self.prefixes else -1
        head = self.prefixes[pick] if pick >= 0 else []
        out: List[Turn] = []
        due = start_s
        prompt = head + self._tokens(sid, 0, self.prompt_len(), 0)
        for k in range(n_turns):
            if k:
                due += self.turn_gap()
                reply = self._tokens(sid, k, out[-1].max_new_tokens, 1)
                prompt = out[-1].prompt + reply + self._tokens(
                    sid, k, self.prompt_len(), 0)
            max_new = self.output_len()
            if len(prompt) + max_new > self.cap:
                break
            out.append(Turn(sid, k, due, prompt, max_new, strict, pick))
        return out

    def open_loop(self, horizon_s: float) -> List[Turn]:
        """Every turn due before ``horizon_s``, in due order (poisson)."""
        turns: List[Turn] = []
        t = 0.0
        while True:
            t += self.gap()
            if t >= horizon_s:
                break
            turns.extend(tr for tr in self.session(t) if tr.due_s < horizon_s)
        turns.sort(key=lambda tr: (tr.due_s, tr.session, tr.index))
        return turns

    def backlog(self) -> Iterator[Turn]:
        """An endless stream of sessions' turns for a saturated backlog;
        a turn is due when it is taken."""
        while True:
            yield from self.session(0.0)


def waiting_target(mix: Dict, max_batch: int) -> Optional[int]:
    """Requests the backlog keeps waiting, or None for an open loop."""
    arrival = mix["arrival"]
    if arrival["kind"] == "backlog":
        return int(arrival["waiting_per_slot"]) * max_batch
    return None
