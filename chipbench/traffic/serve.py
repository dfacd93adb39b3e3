"""Open-loop request traffic: Poisson arrivals at a fixed rate, prompt
and answer lengths from clipped lognormals.

Arrival times and the multiset of (prompt, answer) lengths come from
the cell's own ``base_seed`` and are the same in every run; the run's
``--seed`` only decides which lengths meet which arrival (ramp and
window shuffled apart, so each keeps its own multiset) and which token
ids fill the prompts. Runs with different seeds then do the same work
in another order, and differ no more than two runs of one seed.
"""
from __future__ import annotations

import numpy as np


class Request:
    __slots__ = ("due", "prompt", "answer_len", "in_window", "submitted",
                 "future", "error", "result", "admitted", "first_token",
                 "implied", "completed")

    def __init__(self, due, prompt, answer_len, in_window):
        self.due, self.prompt, self.answer_len = due, prompt, answer_len
        self.in_window = in_window
        self.submitted = self.future = self.error = self.result = None
        # first_token, admitted and implied (completion) are read from
        # the program's spans; completed is what the client observed
        self.admitted = self.first_token = None
        self.implied = self.completed = None


def _lognormal(rng, n, median, sigma, lo, hi):
    x = np.exp(rng.normal(np.log(median), sigma, n))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> list:
    """Requests in due order; ``due`` is seconds after the ramp starts,
    and the window is [ramp_s, ramp_s + seconds)."""
    ramp = float(traffic["ramp_s"])
    horizon = ramp + float(seconds)
    # one stream each for arrivals, prompt and answer lengths, so the
    # schedule of a shorter window is a prefix of a longer one's
    streams = [np.random.default_rng([int(traffic["base_seed"]), k])
               for k in range(3)]
    n_max = int(traffic["rate_per_s"] * horizon * 1.5) + 64
    due = np.cumsum(streams[0].exponential(1.0 / traffic["rate_per_s"],
                                           n_max))
    due = due[due < horizon]
    n = len(due)
    p = traffic["prompt_len"]
    a = traffic["answer_len"]
    prompts = _lognormal(streams[1], n_max, p["median"], p["sigma"],
                         p["min"], p["max"])[:n]
    answers = _lognormal(streams[2], n_max, a["median"], a["sigma"],
                         a["min"], a["max"])[:n]
    rng = np.random.default_rng(seed)
    in_window = due >= ramp
    order = np.arange(n)
    for part in (np.flatnonzero(~in_window), np.flatnonzero(in_window)):
        order[part] = rng.permutation(part)
    out = []
    for i in range(n):
        j = order[i]
        ids = rng.integers(1, vocab, int(prompts[j])).tolist()
        out.append(Request(float(due[i]), ids, int(answers[j]),
                           bool(in_window[i])))
    return out
