"""The one generator of request lists, driven by a mix file in ``traffic/``.

A mix is a closed batch: ``slots`` requests are in flight at every step, and
a request that ends is replaced at once by the next one of the list.  Sizes
come in blocks of ``slots`` requests.  Every block holds the same (prompt,
output) pairs, the stratified quantiles ``(j + 0.5) / slots`` of the two
length distributions, paired and ordered by fixed permutations.  The seed
draws only the token ids.  So every seed asks for the same work in the same
order: a window holds only a few admissions, and an admission's cost grows
with its prompt, so an order drawn from the seed would change the window's
work from seed to seed.

``fill`` says how the first block enters the batch:

* ``fresh``: as new requests, all at the start of their output;
* ``in_flight``: at staggered progress, as in a batch that has run for a
  while.  Each request is left a stratified share of its output as its
  ``max_new``, so requests end, and the next are admitted, from the first
  steps on.  Its prompt is that of its pair, so the fill admits the same
  prompt lengths as every later block.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator

import numpy as np


@dataclasses.dataclass
class Spec:
    rid: int
    prompt: np.ndarray                 # int32 token ids
    max_new: int


def _quantile_lengths(dist: dict, u: np.ndarray) -> np.ndarray:
    if dist["dist"] != "loguniform":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    lo, hi = float(dist["min"]), float(dist["max"])
    x = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def generate(mix: dict, vocab: int, seed: int) -> Iterator[Spec]:
    """The requests in order, without end: the fill block, then block after
    block, each drawn from the seeded generator when it is reached."""
    rng = np.random.default_rng(seed)
    n = int(mix["slots"])
    u = (np.arange(n) + 0.5) / n
    fixed = np.random.default_rng(0)   # the same for every seed
    prompts = _quantile_lengths(mix["prompt_tokens"], u)
    outputs = _quantile_lengths(mix["output_tokens"], fixed.permutation(u))
    shares = fixed.permutation(u)      # in_flight: share of the output done
    order = fixed.permutation(n)
    max_len = int(mix["max_len"])
    if int(prompts.max() + outputs.max()) > max_len:
        raise ValueError(f"prompt + output up to "
                         f"{int(prompts.max() + outputs.max())} tokens "
                         f"exceeds max_len {max_len}")
    if mix["fill"] not in ("fresh", "in_flight"):
        raise ValueError(f"unknown fill {mix['fill']!r}")

    def block(rid0: int, in_flight: bool = False) -> list[Spec]:
        out = []
        for k, j in enumerate(order):
            p, o = int(prompts[j]), int(outputs[j])
            if in_flight:              # the share of the output left
                o = max(1, int(np.ceil((1 - shares[j]) * o)))
            out.append(Spec(rid0 + k, rng.integers(0, vocab, p,
                                                   dtype=np.int32), o))
        return out

    yield from block(0, in_flight=mix["fill"] == "in_flight")
    for b in itertools.count(1):
        yield from block(n * b)
