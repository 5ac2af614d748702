"""The one general traffic generator. A traffic mix is a JSON file of
parameters under benchmarks/traffic/; nothing here knows a mix by name.

``kind: "train"`` — batches of ``batch`` rows of ``seq_len`` tokens, fresh
uniform token ids each step, labels the next token.

``kind: "serve"`` — a closed loop of ``callers``; each caller sends its next
request when the last one finished, and every request decodes greedily.
Lengths come from a fixed grid: ``strata`` values at the midpoints of equal
shares of a log-uniform distribution over ``[prompt_tokens.min,
prompt_tokens.max]`` (and ``output_tokens`` alike). A caller walks the
``strata`` x ``strata`` pairs of prompt and output length in one fixed cycle
that meets every pair once; the callers start at offsets spread evenly over
the cycle (caller 0 at ``cycle_start``, 0 unless the file says otherwise), so
that the mix in flight is the same at any moment.

The seed draws the token ids (and, in the entry, the weights) and NOT the
lengths or their order: in a closed loop the lengths are the arrivals, a
window holds two or three cycles, and when the seed turned the cycle the
mean time to first token moved by 6% from seed to seed against 0.3% between
two runs of one seed (my chip runs, PR 24). So every seed offers one
schedule; ``cycle_start`` gives another to hold a claim against (PERF.md).
A caller's first request has its output cut to the share
``(caller + 0.5) / callers``, so that in-flight requests stand at evenly
scattered phases when the window opens, as in steady state.

``sessions`` (optional) — ``count`` conversations whose contexts, of
``context_tokens`` (a grid of ``count`` lengths as above), are served once in
set-up; caller ``c`` talks in session ``c mod count`` and every prompt of its
stream is that context followed by a new turn of the drawn prompt length. The
turn's tokens are unique to the request, so only the context can come from a
prefix cache.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (the driver's seeds
    pass 2**31, which int32 does not hold)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def train_batches(traffic: Dict[str, Any], vocab: int,
                  seed: int) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Endless (ids, labels) int64 [batch, seq_len]; every row differs."""
    rng = np.random.default_rng([int(seed), 1])
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    while True:
        toks = rng.integers(0, vocab, (b, s + 1))
        yield toks[:, :-1], toks[:, 1:]


def length_grid(spec: Dict[str, int], strata: int) -> List[int]:
    lo, hi = math.log(spec["min"]), math.log(spec["max"])
    return [int(round(math.exp(lo + (hi - lo) * (i + 0.5) / strata)))
            for i in range(strata)]


def session_contexts(traffic: Dict[str, Any], vocab: int,
                     seed: int) -> List[np.ndarray]:
    """The token ids of each session's context ([] without ``sessions``)."""
    sessions = traffic.get("sessions")
    if not sessions:
        return []
    count = int(sessions["count"])
    return [np.random.default_rng([int(seed), 5, s]).integers(0, vocab, (n,))
            for s, n in enumerate(length_grid(sessions["context_tokens"],
                                              count))]


class CallerStream:
    """The endless request stream of one caller: (prompt ids, max_new)."""

    def __init__(self, traffic: Dict[str, Any], vocab: int, seed: int,
                 caller: int):
        self.rng = np.random.default_rng([int(seed), 2, caller])
        self.vocab = vocab
        self.strata = int(traffic["strata"])
        self.prompts = length_grid(traffic["prompt_tokens"], self.strata)
        self.outputs = length_grid(traffic["output_tokens"], self.strata)
        callers = int(traffic["callers"])
        cycle = self.strata * self.strata
        self.at = (int(traffic.get("cycle_start", 0))
                   + (caller * cycle) // callers)
        self.first_share = (caller + 0.5) / callers
        contexts = session_contexts(traffic, vocab, seed)
        self.context = (contexts[caller % len(contexts)] if contexts
                        else np.zeros(0, np.int64))

    def lengths(self) -> Tuple[int, int]:
        """The next (prompt, output) pair of the cycle: place a*S + b holds
        prompt b and output (a + b) mod S, which meets every pair once."""
        j = self.at % (self.strata * self.strata)
        self.at += 1
        a, b = divmod(j, self.strata)
        return self.prompts[b], self.outputs[(a + b) % self.strata]

    def next(self) -> Tuple[np.ndarray, int]:
        n_prompt, n_out = self.lengths()
        turn = self.rng.integers(0, self.vocab, (n_prompt,))
        return np.concatenate([self.context, turn]), int(n_out)

    def first_ramp(self) -> Tuple[np.ndarray, int]:
        prompt, n_out = self.next()
        return prompt, max(1, int(n_out * self.first_share))
