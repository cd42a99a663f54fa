"""gen_lag_p99_ms: how late the open-loop generator sent requests: 99th
percentile of submit time minus due time over the requests it sent in
the window.  A starved generator shows here, not as a fast server."""

from bench.stats import percentile


def read(rec):
    lags = [r.submitted - r.due for r in rec.due_in_window()
            if r.submitted is not None]
    p = percentile(lags, 99)
    return None if p is None else p * 1e3
