"""ttft_p90_ms: time to first token, 90th percentile over every request
due in the window, from when it was due (not when it was sent) to when
the client received its first token.  A request still without a token
when the window closes counts at its wait so far."""

from bench.stats import percentile


def read(rec):
    waits = []
    for r in rec.due_in_window():
        first = r.recv[0] if r.recv and r.recv[0] <= rec.seconds \
            else rec.seconds
        waits.append(first - r.due)
    p = percentile(waits, 90)
    return None if p is None else p * 1e3
