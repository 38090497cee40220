"""The one general generator of traffic.  A mix is a data file of
parameters (``benchmark/workloads/<cell>.json``, key ``traffic``); this
file turns it and a seed into inputs.  It never imports jax or the
program.

Kinds:

``token_batches``  training: an endless stream of ``(batch, seq)`` uniform
                   token ids with next-token labels, each row different.
``open_loop``      serving: Poisson arrivals at a fixed rate, prompt and
                   output lengths log-normal and clipped.

Every seed gives the same *set* of sizes and of arrival instants: both are
drawn once from a fixed stream of the mix (not of the seed), and the seed
deals the sizes onto the instants in another order and draws the token
ids, so that it changes which request comes when, not the amount of work.
The seed permutes the sizes only within blocks of ``shuffle_block``
consecutive arrivals, so that the load offered over any few seconds is the
same for every seed too: a window that holds some tens of long requests
otherwise reads its tails off which of them came last.
"""

from __future__ import annotations

import math

import numpy as np


def rng_of(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


class TokenBatches:
    """``next()`` gives {"input_ids", "labels"} as int32 numpy arrays."""

    def __init__(self, params: dict, vocab_size: int, seed: int):
        self.batch = int(params["batch"])
        self.seq = int(params["seq"])
        self.vocab = int(vocab_size)
        self.tokens_per_batch = self.batch * self.seq
        self._rng = rng_of(seed, 1)

    def next(self) -> dict:
        ids = self._rng.integers(0, self.vocab, (self.batch, self.seq),
                                 dtype=np.int32)
        return {"input_ids": ids, "labels": np.roll(ids, -1, axis=1)}


def _lognormal_clipped(rng, n, median, sigma, lo, hi):
    x = np.exp(rng.normal(math.log(median), sigma, size=n))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _dealt(rng, n: int, block: int) -> np.ndarray:
    """A permutation of ``range(n)`` that moves nothing out of its block of
    ``block`` consecutive places: the sizes come in another order for every
    seed while the load offered over any few seconds stays the same."""
    order = np.arange(n)
    for a in range(0, n, max(1, block)):
        order[a:a + block] = rng.permutation(order[a:a + block])
    return order


def open_loop_schedule(params: dict, vocab_size: int, seed: int,
                       horizon_s: float, rate_rps: float = None) -> list:
    """Requests due in ``[0, horizon_s)``: a list of dicts with ``due_s``,
    ``prompt`` (token ids), ``max_tokens``.  The count is
    ``round(rate * horizon)`` for every seed, and so are the instants (a
    Poisson process conditioned on its count, from the mix's own stream);
    the seed draws which lengths come at which instant, and the ids."""
    rate = float(params["rate_rps"] if rate_rps is None else rate_rps)
    n = max(1, int(round(rate * horizon_s)))
    fixed = np.random.default_rng([0, n])     # the mix's stream, not the seed's
    p = params["prompt_tokens"]
    o = params["output_tokens"]
    plen = _lognormal_clipped(fixed, n, p["median"], p["sigma"],
                              p["min"], p["max"])
    olen = _lognormal_clipped(fixed, n, o["median"], o["sigma"],
                              o["min"], o["max"])
    # arrivals: a Poisson process conditioned on its count is n sorted
    # uniforms
    due = np.sort(fixed.uniform(0.0, horizon_s, size=n))
    rng = rng_of(seed, 2)
    order = _dealt(rng, n, int(params["shuffle_block"]))
    plen, olen = plen[order], olen[order]
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab_size, size=int(plen[i]),
                              dtype=np.int64).tolist()
        out.append({"index": i, "due_s": float(due[i]), "prompt": prompt,
                    "max_tokens": int(olen[i])})
    return out
