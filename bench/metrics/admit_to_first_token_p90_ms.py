"""admit_to_first_token_p90_ms: the service part of time to first
token: 90th percentile of first token emitted minus admission, over
every request due in the window that was admitted in it.  Both are the
engine's stamps (``RequestResult.admitted_us`` and the first
``StreamEvent.t_us``, which is ``first_token_us``), as ``Req.admitted``
and ``Req.emitted[0]`` in window seconds.  It holds the prefill and
the ticks until the first token is read back; a request admitted but
without a token when the window closes counts at its wait so far.
Reads nothing from a record that carries no such stamps."""

from bench.stats import percentile


def read(rec):
    waits = []
    for r in rec.due_in_window():
        admitted = getattr(r, "admitted", None)
        if admitted is None or admitted > rec.seconds:
            continue
        emitted = getattr(r, "emitted", None) or []
        first = emitted[0] if emitted and emitted[0] <= rec.seconds \
            else rec.seconds
        waits.append(first - admitted)
    p = percentile(waits, 90)
    return None if p is None else p * 1e3
