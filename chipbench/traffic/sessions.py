"""Multi-turn chat sessions, replayed open-loop.

``sessions`` sessions start as a Poisson process at ``session_rate_per_s``
(a sweep passes its own rate). Each opens
with one of ``system_prompts`` shared system prompts (chosen by Zipf with
exponent ``system_zipf``) and runs ``turns`` user turns apart by exponential
gaps. Turn t's prompt is the system prompt, every earlier user turn and its
recorded answer, and user turn t; it asks for as many new tokens as its
recorded answer holds. A session ends before the turn whose prompt and answer
would pass ``context`` tokens. Requests due at or after the window's end are
not sent.

The schedule (session starts, turn counts and gaps, system prompt choice,
lengths) is drawn once from the mix's ``schedule_seed``, from the
distributions' quantiles at evenly spaced points; ``--seed`` draws every
token id (system prompts, user turns, recorded answers). So every seed sends
the same requests at the same times with other contents: the amount of work
in the window does not depend on the seed. The session starts are drawn at
rate 1 and divided by the rate, so every rate sends the same sessions, their
starts compressed or stretched, and a sweep over rates changes nothing else.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Turn:
    due_s: float
    prompt: np.ndarray      # (S,) int32
    max_new: int
    session: int
    turn: int


def _quantiles(n: int, inv_cdf) -> np.ndarray:
    return np.asarray([inv_cdf((i + 0.5) / n) for i in range(n)])


def _lognormal(spec: dict):
    mu, sig = math.log(spec["median"]), spec["sigma"]

    def inv(u):
        from statistics import NormalDist
        x = math.exp(mu + sig * NormalDist().inv_cdf(u))
        return int(min(max(round(x), spec["min"]), spec["max"]))
    return inv


def _zipf_inv(n: int, a: float):
    w = np.asarray([1.0 / (k + 1) ** a for k in range(n)])
    cdf = np.cumsum(w / w.sum())

    def inv(u):
        return int(np.searchsorted(cdf, u))
    return inv


def make(traffic: dict, model: dict, seed: int, seconds: float,
         rate: float = None) -> List[Turn]:
    """The turns due in [0, seconds), sorted by due time."""
    rng = np.random.default_rng([int(traffic["schedule_seed"]), 0x5E55])
    tok = np.random.default_rng([int(seed), 0x70C])
    rate = traffic["session_rate_per_s"] if rate is None else rate
    n = int(traffic["sessions"])
    lo, hi = traffic["turns"]
    V = model["vocab_size"]

    gaps = rng.permutation(_quantiles(n, lambda u: -math.log(1 - u)))
    starts = (np.cumsum(gaps) - gaps[0]) / rate
    n_turns = rng.permutation(
        [lo + (i % (hi - lo + 1)) for i in range(n)])
    systems = rng.permutation(_quantiles(
        n, _zipf_inv(traffic["system_prompts"], traffic["system_zipf"])))
    n_all = int(sum(n_turns))
    users = rng.permutation(_quantiles(n_all,
                                       _lognormal(traffic["user_tokens"])))
    answers = rng.permutation(_quantiles(
        n_all, _lognormal(traffic["answer_tokens"])))
    mean_gap = traffic["turn_gap_mean_s"]
    turn_gaps = rng.permutation(
        _quantiles(n_all, lambda u: -math.log(1 - u) * mean_gap))
    sys_prompts = [tok.integers(0, V, traffic["system_prompt_tokens"],
                                dtype=np.int32)
                   for _ in range(traffic["system_prompts"])]

    out: List[Turn] = []
    k = 0
    for s in range(n):
        hist = [sys_prompts[int(systems[s])]]
        due = float(starts[s])
        for t in range(int(n_turns[s])):
            user = tok.integers(0, V, int(users[k]), dtype=np.int32)
            answer = tok.integers(0, V, int(answers[k]), dtype=np.int32)
            if t:
                due += float(turn_gaps[k])
            k += 1
            prompt = np.concatenate(hist + [user])
            if len(prompt) + len(answer) > traffic["context"]:
                k += int(n_turns[s]) - t - 1
                break
            if due < seconds:
                out.append(Turn(due, prompt, len(answer), s, t))
            hist += [user, answer]
    return sorted(out, key=lambda r: r.due_s)
