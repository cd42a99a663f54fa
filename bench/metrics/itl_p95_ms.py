"""itl_p95_ms: 95th percentile of every gap between two consecutive
tokens of one request, both received in the window."""

from bench.stats import percentile


def read(rec):
    gaps = []
    for r in rec.requests:
        t = [x for x in r.recv if rec.in_window(x)]
        gaps += [b - a for a, b in zip(t, t[1:])]
    p = percentile(gaps, 95)
    return None if p is None else p * 1e3
