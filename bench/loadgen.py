"""The one traffic generator: reads a mix's parameters and makes the
requests of a run from ``--seed``.

Every seed gets the same work: the same sizes and arrivals in the same
order, and other prompt tokens.  Sizes and gaps are the quantiles of
the mix's distributions at evenly spaced probabilities, laid out in an
order drawn from the mix's own fixed ``arrangement`` seed; ``--seed``
draws only the prompts' tokens.  The order is fixed too because an
open loop's tail hangs on it: on the chip, six seeds that each shuffled
the same chat sizes and gaps read a TTFT p90 from 272 to 1809 ms, while
two runs of one seed agreed within 10 %.  So the spread between runs is
the system's, not the draw's.

Two loops:

* ``open``: requests are due at fixed times (Poisson arrivals at
  ``rate_per_s``: exponential gaps), whatever the server does.  Each is
  timed from when it was due.
* ``closed``: ``clients`` clients each send their next request when the
  last has ended.  The first request of each client asks for a residual
  share of its output, so the run opens with the clients at different
  points of their answers, as in steady state.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, List, Mapping, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Item:
    """One request as the generator plans it."""

    uid: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due: Optional[float] = None   # open loop: seconds after window start
    client: int = 0               # closed loop: the client that sends it


def quantiles(dist: Mapping[str, Any], n: int) -> np.ndarray:
    """``n`` integer sizes at probabilities (i + 0.5) / n of ``dist``,
    clipped to its ``min`` and ``max``."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in p])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = lo + p * (hi - lo)
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential gaps (Poisson arrivals at ``rate``) at evenly
    spaced probabilities."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def _prompt(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    # the last id is the program's end-of-sequence token: keep it out
    return rng.integers(0, vocab - 1, n).astype(np.int32)


def plan(traffic: Mapping[str, Any], seed: int, seconds: float,
         vocab: int) -> List[Item]:
    """The requests of one run, in the order they are due (open loop) or
    by client and turn (closed loop)."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(traffic["arrangement"])
    if traffic["loop"] == "open":
        n = max(1, round(traffic["rate_per_s"] * seconds))
        gaps = order.permutation(exp_gaps(traffic["rate_per_s"], n))
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        plens = order.permutation(quantiles(traffic["prompt_tokens"], n))
        outs = order.permutation(quantiles(traffic["output_tokens"], n))
        return [Item(uid=i, prompt=_prompt(rng, int(plens[i]), vocab),
                     max_new=int(outs[i]), due=float(due[i]))
                for i in range(n)]
    if traffic["loop"] == "closed":
        c, turns = traffic["clients"], traffic["requests_per_client"]
        items: List[Item] = []
        for turn in range(turns):
            plens = order.permutation(
                quantiles(traffic["prompt_tokens"], c))
            outs = quantiles(traffic["output_tokens"], c)
            if turn == 0:
                # the rest of an answer already under way: the answer
                # sizes paired with shares (i + 0.5) / c, in reverse
                share = (np.arange(c)[::-1] + 0.5) / c
                outs = np.maximum(1, np.ceil(share * outs)).astype(int)
            outs = order.permutation(outs)
            for k in range(c):
                out = int(outs[k])
                items.append(Item(uid=len(items),
                                  prompt=_prompt(rng, int(plens[k]), vocab),
                                  max_new=out, client=k))
        return items
    raise ValueError(f"unknown loop {traffic['loop']!r}")
