"""The one traffic generator: a mix file's parameters in, jobs out.

A mix file (``bench/traffic/<name>.json``) names a ``kind`` and the
parameters of its lengths, arrivals and sharing.  One kind exists:

``sessions``  ``sessions`` multi-turn chats.  Each session opens with
              one of ``system_prompts`` (``count`` prompts, lengths from
              ``length``, Zipf popularity ``zipf_s``), or with nothing
              where the mix has none; each turn appends a ``user`` message
              to the history and asks for an ``answer``; a session ends
              after ``max_turns`` turns or ``max_tokens`` tokens and a
              fresh one takes its place.  With ``rate_per_s``, turns
              arrive at that rate with exponential gaps and each goes to
              the session idle longest; without it, every session sends
              its next turn as soon as its answer completes (closed loop,
              no think time), starting with a turn of each session in
              set-up.
              Set-up first serves every session's first turn, with an
              answer drawn from ``answer`` in place of a generated one, so
              the window opens on sessions with history.  Sessions of one
              turn have none to build: in a closed loop, set-up places
              each part-way through an answer instead (:meth:`_mid_answer`),
              so the window opens as in a loop that has run a while.

Every length distribution (``lognormal`` with ``median`` and ``sigma``,
``uniform``, ``exponential`` with ``mean``, ``fixed`` with ``value``;
``min``/``max`` clip) is drawn *stratified*: each block of 64 draws is
the distribution's 64 quantiles at (i + 0.5) / 64, in an order shuffled
by the seed.  Every seed therefore
sends the same sizes in each block, in another order; with
``same_order``, in the same order, and every other choice of the mix
(arrivals, system prompts, sessions) is the same for every seed too.
Token ids are uniform over the vocabulary, from the seed.
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque
from statistics import NormalDist
from typing import Deque, Dict, List, Optional

import numpy as np

BLOCK = 64


def quantile(dist: Dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        return dist["median"] * math.exp(dist["sigma"]
                                         * NormalDist().inv_cdf(u))
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    if kind == "exponential":
        return -dist["mean"] * math.log1p(-u)
    if kind == "fixed":
        return dist["value"]
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: Dict, rng: np.random.Generator, n: int = BLOCK,
               integer: bool = True) -> np.ndarray:
    """``n`` draws of ``dist``: its quantiles at (i + 0.5) / n, clipped
    to ``[min, max]``, in an order shuffled by ``rng``."""
    vals = np.array([quantile(dist, (i + 0.5) / n) for i in range(n)])
    lo, hi = dist.get("min", -np.inf), dist.get("max", np.inf)
    vals = np.clip(vals, lo, hi)
    if integer:
        vals = np.clip(np.rint(vals), lo, hi).astype(np.int64)
    return vals[rng.permutation(n)]


class _Stream:
    """An endless stratified stream of one distribution."""

    def __init__(self, dist: Dict, rng: np.random.Generator,
                 integer: bool = True):
        self.dist, self.rng, self.integer = dist, rng, integer
        self.buf: Deque = deque()

    def next(self):
        if not self.buf:
            self.buf.extend(stratified(self.dist, self.rng,
                                       integer=self.integer).tolist())
        return self.buf.popleft()


@dataclasses.dataclass
class Job:
    """One request to submit: ``due`` is its scheduled arrival in seconds
    from the window's start (None: as soon as it is released)."""

    prompt: np.ndarray
    max_new: int
    tag: int
    due: Optional[float] = None


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


class Sessions:
    """The ``sessions`` mix.  The harness calls :meth:`setup_jobs` (served
    to completion in set-up), :meth:`first_jobs` (submitted in set-up, run
    on into the window), :meth:`due_jobs` (released by window time ``t``)
    and :meth:`on_done` (the follow-ups of a completed job)."""

    def __init__(self, spec: Dict, seed: int, vocab: int):
        self.spec, self.seed, self.vocab = spec, seed, vocab
        self._n_tokens = 0
        order = 0 if spec.get("same_order") else seed
        sp = spec.get("system_prompts")
        if sp is None:
            self.system = [np.zeros(0, np.int32)]
            self.popularity = np.ones(1)
        else:
            lens = stratified(sp["length"], _rng(order, 6), n=sp["count"])
            self.system = [self.tokens(n) for n in lens]
            rank = np.arange(1, sp["count"] + 1, dtype=np.float64)
            w = rank ** -sp["zipf_s"]
            self.popularity = w / w.sum()
        self._sys_order: Deque[int] = deque()
        self._sys_rng = _rng(order, 7)
        self.closed = "rate_per_s" not in spec
        if not self.closed:
            self.gaps = _Stream({"dist": "exponential",
                                 "mean": 1.0 / spec["rate_per_s"]},
                                _rng(order, 5), integer=False)
        self.user = _Stream(spec["user"], _rng(order, 2))
        self.answer = _Stream(spec["answer"], _rng(order, 3))
        self.history: List[np.ndarray] = []
        self.turns: List[int] = []
        for _ in range(spec["sessions"]):
            self.history.append(self._fresh())
            self.turns.append(0)
        self.idle: Deque[int] = deque(
            _rng(order, 8).permutation(spec["sessions"]).tolist())
        self.t_next = None if self.closed else self.gaps.next()
        self.backlog: Deque[float] = deque()

    def tokens(self, n: int) -> np.ndarray:
        self._n_tokens += 1
        return _rng(self.seed, 1, self._n_tokens).integers(
            1, self.vocab, size=int(n)).astype(np.int32)

    def _fresh(self) -> np.ndarray:
        """A new session's history: one system prompt, drawn so that each
        block of 64 sessions follows the Zipf popularity exactly."""
        if not self._sys_order:
            counts = np.floor(self.popularity * BLOCK).astype(int)
            rest = BLOCK - counts.sum()
            counts[np.argsort(-(self.popularity * BLOCK - counts))[:rest]] += 1
            order = np.repeat(np.arange(len(counts)), counts)
            self._sys_order.extend(order[self._sys_rng.permutation(BLOCK)]
                                   .tolist())
        return self.system[self._sys_order.popleft()]

    def _turn(self, s: int, due: Optional[float],
              answer: Optional[np.ndarray] = None) -> Job:
        user = self.tokens(self.user.next())
        max_new = int(self.answer.next())
        if (self.turns[s] >= self.spec["max_turns"]
                or len(self.history[s]) + len(user) + max_new
                > self.spec["max_tokens"]):
            self.history[s], self.turns[s] = self._fresh(), 0
        parts = [self.history[s], user]
        if answer is not None:
            parts.append(self.tokens(max_new))
            max_new = answer
        return Job(np.concatenate(parts).astype(np.int32), max_new, s, due)

    def setup_jobs(self) -> List[Job]:
        if self.spec["max_turns"] <= 1:
            return []
        # a first turn whose answer is drawn, not generated: the prompt
        # carries it, and the set-up request decodes just enough to warm
        # the decode step
        jobs = [self._turn(s, None, answer=2)
                for s in range(self.spec["sessions"])]
        for job in jobs:
            self.history[job.tag] = job.prompt
            self.turns[job.tag] = 1
        return jobs

    def first_jobs(self) -> List[Job]:
        if not self.closed:
            return []
        if self.spec["max_turns"] <= 1:
            return self._mid_answer()
        jobs = [self._turn(s, None) for s in self.idle]
        self.idle.clear()
        return jobs

    def _mid_answer(self) -> List[Job]:
        """The first turns of one-turn sessions, each part-way through an
        answer, as a closed loop that has run a while holds them.  The
        answers are ``answer``'s 64 quantiles laid end to end, each as
        long as its tokens; session j stands at (j + 0.5) / sessions of
        the whole, so a long answer, which holds its slot longer, is met
        more often, at a point spread over its length.  The part already
        served rides in the prompt after a ``user`` message (that
        distribution's quantiles, in a fixed order), and the job asks for
        the rest.  Every seed places the same points."""
        n = len(self.idle)
        answers = np.sort(stratified(self.spec["answer"], _rng(0)))
        ends = np.cumsum(answers)
        at = (np.arange(n) + 0.5) / n * ends[-1]
        which = np.searchsorted(ends, at, side="right")
        served = (at - ends[which] + answers[which]).astype(np.int64)
        users = stratified(self.spec["user"], _rng(0, 1), n=n)
        jobs = []
        for j, s in enumerate(self.idle):
            prompt = np.concatenate([self.history[s], self.tokens(users[j]),
                                     self.tokens(served[j])])
            jobs.append(Job(prompt.astype(np.int32),
                            int(answers[which[j]] - served[j]), s))
        self.idle.clear()
        return jobs

    def due_jobs(self, t: float) -> List[Job]:
        if self.closed:
            return []
        while self.t_next <= t:
            self.backlog.append(self.t_next)
            self.t_next += self.gaps.next()
        out = []
        while self.backlog and self.idle:
            out.append(self._turn(self.idle.popleft(), self.backlog.popleft()))
        return out

    def on_done(self, job: Job, prompt: np.ndarray, output: List[int],
                t: float) -> List[Job]:
        s = job.tag
        self.history[s] = np.concatenate(
            [prompt, np.asarray(output, np.int32)])
        self.turns[s] += 1
        if self.closed:
            return [self._turn(s, None)]
        self.idle.append(s)
        return self.due_jobs(t)


KINDS = {"sessions": Sessions}


def make_mix(spec: Dict, seed: int, vocab: int) -> Sessions:
    return KINDS[spec["kind"]](spec, seed, vocab)
