"""The check that decides ``correct``: what the window served, against
the plain reference of the configuration's family
(``bench/families/<family>.py``).

The reference imports nothing of the program and takes nothing the
program made: it remakes the weights from the seed one layer at a time
and runs one sequence at a time in float32 at ``highest`` matmul
precision, after the program's state is freed.

Once the window has closed, a sample of the requests the server
finished is drawn from the seed, the longest among them.  The
reference runs once over each prompt with its served tokens, and at
each served position reads the gap by which the served token's logit
lies below its own best.  The widest gap is the number compared with
the configuration's limit.  Served tokens are greedy, so a sound
program's gaps are rounding; a wrong token, position or cache entry
shows as a gap of the size of the logits' spread.

The control puts the same reference, computed in float8 (e4m3, one
scale per tensor, for every matmul operand), in the program's place:
at each position of the same prompts and tokens, the token it puts
first is read against the float32 reference's best, and the widest of
those gaps is held to the same limit.  A sound limit fails it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

# the sample: at least this many served tokens, in at least MIN_REQS and
# at most MAX_REQS finished requests
SAMPLE_TOKENS = 300
MIN_REQS = 2
MAX_REQS = 8


@dataclasses.dataclass
class Check:
    """The outcome of the comparison: each number beside its limit, and
    the reading the other side gave (the program's, in a control run)."""

    correct: bool
    numbers: Dict[str, Dict[str, Any]]
    other: Dict[str, float] = dataclasses.field(default_factory=dict)

    def lines(self) -> List[str]:
        return [f"{k}: {v['value']} (limit {v['limit']})"
                for k, v in self.numbers.items()]


def _positions(seqs_rows):
    return jnp.concatenate([h[jnp.asarray(pos)] for h, pos in seqs_rows])


def served_gaps(fam, conf: Dict[str, Any], seed: int,
                reqs: List[Tuple[np.ndarray, List[int]]],
                control: bool = False) -> Dict[str, np.ndarray]:
    """For each (prompt, served tokens), the float32 reference's gap at
    every served position: best logit minus the served token's.  With
    ``control``, also the gap of the token the float8 reference puts
    first there.  Every sequence is padded to the engine's cache length,
    so the reference compiles one shape."""
    length = conf["engine"]["cache_len"]
    seqs = [np.concatenate([p, np.asarray(t[:-1], np.int32)])
            for p, t in reqs]
    pos = [np.arange(len(p) - 1, len(p) - 1 + len(t)) for p, t in reqs]
    served = np.concatenate([np.asarray(t, np.int64) for _, t in reqs])
    out: Dict[str, np.ndarray] = {}
    picks = None
    if control:
        rows = _positions(zip(fam.hidden(conf, seed, seqs, length, True),
                              pos))
        picks = np.concatenate([np.asarray(jnp.argmax(lg, axis=-1))
                                for _, lg in fam.head_blocks(conf, seed,
                                                             rows, True)])
        del rows
    rows = _positions(zip(fam.hidden(conf, seed, seqs, length), pos))
    gaps, ctrl = [], []
    for i, lg in fam.head_blocks(conf, seed, rows):
        n = lg.shape[0]
        best = jnp.max(lg, axis=-1)
        idx = jnp.arange(n)
        gaps.append(np.asarray(best - lg[idx, served[i:i + n]]))
        if picks is not None:
            ctrl.append(np.asarray(best - lg[idx, picks[i:i + n]]))
    out["served"] = np.concatenate(gaps)
    if picks is not None:
        out["control"] = np.concatenate(ctrl)
    return out


def sample(rec, seed: int) -> list:
    """The finished requests to compare, drawn from the seed: the
    longest, then others in a seeded order until the sample holds
    ``SAMPLE_TOKENS`` served tokens."""
    done = [r for r in rec.requests
            if r.done and r.error is None and len(r.tokens) == r.max_new]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.tokens), r.uid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 1]).permutation(len(rest))
    picked = [longest]
    for i in order:
        if (len(picked) >= MAX_REQS
                or (len(picked) >= MIN_REQS
                    and sum(len(r.tokens) for r in picked)
                    >= SAMPLE_TOKENS)):
            break
        picked.append(rest[i])
    return picked


def check(cell, seed: int, rec, control: bool = False) -> Check:
    """Compare a sample of what the window served with the reference.
    With ``control`` the float8 reference stands in the program's place
    on the same sample, and decides ``correct``; the program's own
    widest gap is kept in ``other``."""
    limit = cell.conf["check"]["widest_gap_limit"]
    picked = sample(rec, seed)
    numbers: Dict[str, Dict[str, Any]] = {
        "requests_compared": {"value": len(picked), "limit": ">= 1"},
    }
    if not picked:
        numbers["widest_gap"] = {"value": None, "limit": limit}
        return Check(False, numbers)
    reqs = [(r.prompt, r.tokens) for r in picked]
    gaps = served_gaps(cell.family, cell.conf, seed, reqs, control=control)
    served = float(gaps["served"].max())
    numbers["tokens_compared"] = {"value": int(gaps["served"].size),
                                  "limit": ">= 1"}
    if not control:
        numbers["widest_gap"] = {"value": served, "limit": limit}
        return Check(served <= limit, numbers)
    ctrl = float(gaps["control"].max())
    numbers["widest_gap"] = {"value": ctrl, "limit": limit}
    return Check(ctrl <= limit, numbers, other={"program_widest_gap": served})
