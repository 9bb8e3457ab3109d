"""The generator of closed-backlog mixes: a traffic mix is a data file of
parameters beside this file, whose `generator` key names the file that
reads it.  A later PR adds a mix of this kind as a data file; a kind of
traffic this does not know (timed arrivals, shared prefixes) comes as a
generator file of its own beside it.

Every seed gets the SAME multiset of sizes (drawn from the file's
`shape_seed`), in another order, and its own token ids: the seed then
changes which request meets which, not how much work a run holds.  The
sizes are drawn for `block` requests only and the list is that block over
and over, each copy in an order of its own: a window that sees a tenth of
the list then sees the same sizes whatever the seed (without it, the
served tokens/s spread by 23% from seed to seed; my chip runs, PR 27).

Mix file keys:
  generator    "backlog": this file
  count        requests in the list, all due at once, before the window
  block        sizes drawn; divides count
  prompt_len, output_len   {"lo", "hi"}: log-uniform on [lo, hi], whole
  max_total    prompt + output are clipped to it (the output gives way)
  shape_seed   what the sizes are drawn from
"""
from __future__ import annotations

import numpy as np


def _loguniform(spec: dict, n: int, rng) -> np.ndarray:
    u = rng.uniform(np.log(spec["lo"]), np.log(spec["hi"]), n)
    return np.clip(np.rint(np.exp(u)).astype(np.int64),
                   spec["lo"], spec["hi"])


def requests(mix: dict, seed: int, vocab: int) -> list:
    """[{"prompt": int32 array, "max_new": int}], in the order they are
    submitted."""
    n, block = int(mix["count"]), int(mix["block"])
    if n % block:
        raise ValueError(f"block {block} does not divide count {n}")
    shape = np.random.default_rng(int(mix["shape_seed"]))
    P = _loguniform(mix["prompt_len"], block, shape)
    N = _loguniform(mix["output_len"], block, shape)
    N = np.maximum(1, np.minimum(N, int(mix["max_total"]) - P))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    order = np.concatenate([rng.permutation(block)
                            for _ in range(n // block)])
    return [{"prompt": rng.integers(0, vocab, int(P[j]), dtype=np.int32),
             "max_new": int(N[j])} for j in order]
