"""Closed-loop serving traffic: ``clients`` callers that each wait for their
reply before sending their next request.

With as many clients as the deployment has lanes this is interactive traffic
at a fixed number of seats; with more clients than lanes the surplus always
waits, which is a saturated backlog. Parameters (the traffic file's
``params``):

- ``clients``: how many callers;
- ``prompt``, ``answer``: length distributions, ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
- ``first_round_answer``: optional distribution that cuts each client's first
  answer short, so the lanes do not march in step;
- ``schedule_seed``: the lengths of client c's k-th request are drawn from
  this and (c, k) alone. ``--seed`` draws the token values. So every seed
  offers the same sizes in the same places, and only the content differs:
  runs of different seeds do the same work.

Every request has distinct random tokens: nothing is shared between prompts.
"""
from __future__ import annotations

import numpy as np

KIND = "serve"


def _draw(rng, dist):
    if dist["dist"] == "uniform":
        return int(rng.integers(dist["min"], dist["max"] + 1))
    if dist["dist"] == "lognormal":
        x = np.exp(rng.normal(np.log(dist["median"]), dist["sigma"]))
        return int(min(max(round(x), dist["min"]), dist["max"]))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


class ClosedLoop:
    def __init__(self, params, seed, *, vocab_size, max_seq_len):
        self.p = params
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.max_seq_len = int(max_seq_len)
        self.clients = int(params["clients"])
        self._round = [0] * self.clients

    def lengths(self, client, k):
        """(prompt tokens, answer tokens) of client ``client``'s k-th
        request: a function of the traffic file alone."""
        rng = np.random.default_rng(
            [int(self.p["schedule_seed"]), client, k])
        prompt = _draw(rng, self.p["prompt"])
        answer = _draw(rng, self.p["answer"])
        cut = self.p.get("first_round_answer")
        if k == 0 and cut:
            answer = min(answer, _draw(rng, cut))
        return prompt, min(answer, self.max_seq_len - prompt)

    def _next(self, client):
        k = self._round[client]
        self._round[client] += 1
        prompt, answer = self.lengths(client, k)
        tokens = np.random.default_rng([self.seed, client, k]).integers(
            0, self.vocab_size, prompt, dtype=np.int32)
        return {"key": (client, k), "prompt": tokens, "answer": answer}

    def start(self):
        """The requests offered before the first step."""
        return [self._next(c) for c in range(self.clients)]

    def after_step(self, now, finished):
        """The requests offered after a step: each client whose request
        just finished (``finished``: request keys) sends its next one."""
        return [self._next(client) for client, _ in finished]


def build(params, seed, **deployment):
    return ClosedLoop(params, seed, **deployment)
