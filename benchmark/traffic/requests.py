"""Serving traffic: one general request generator; a mix is its parameter
file (``traffic/<name>.json``, ``"kind": "requests"``).

Every seed gets the same (prompt length, output length) pairs — ``n_sizes``
mid-quantiles of the clipped lognormals, in one order fixed by the mix's own
``sizes_seed`` — and its own token ids; the clients take them round after
round.  A window is shorter than a round of a slow server, so another order
would be other work: the seed changes the content only.
"""

from __future__ import annotations

import numpy as np


def _clipped_lognormal(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of a lognormal, clipped: the same
    distribution at any ``n``, with no luck of the draw in it."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def size_table(traffic: dict) -> np.ndarray:
    """``(n_sizes, 2)``: prompt and output lengths in the order they are sent,
    the same for every seed; the order, and which output length goes with
    which prompt, are fixed by ``sizes_seed``."""
    n = traffic["n_sizes"]
    rs = np.random.RandomState(traffic["sizes_seed"])
    order, pairing = rs.permutation(n), rs.permutation(n)
    return np.stack(
        [_clipped_lognormal(traffic["prompt_tokens"], n)[order], _clipped_lognormal(traffic["max_new_tokens"], n)[pairing]],
        axis=1,
    )


def make_requests(traffic: dict, vocab: int, seed: int):
    """Yields requests ``{"prompt": [...], "max_new_tokens": n, "temperature":
    t}`` without end, in the order the clients take them."""
    table = size_table(traffic)
    rs = np.random.RandomState(seed % (2**32))
    while True:
        for n_prompt, n_new in table.tolist():
            yield {
                "prompt": rs.randint(0, vocab, size=n_prompt).tolist(),
                "max_new_tokens": n_new,
                "temperature": traffic["temperature"],
            }
