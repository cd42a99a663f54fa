"""token_delivery_p99_ms: how late tokens reached the client after the
engine emitted them (``StreamingServer``'s per-request queues and the
receiving thread): 99th percentile of received minus emitted over every
token received in the window.  Emission is the token's
``StreamEvent.t_us``, as ``Req.emitted`` in window seconds beside
``Req.recv``.  Reads nothing from a record that carries no such
stamps."""

from bench.stats import percentile


def read(rec):
    late = []
    for r in rec.requests:
        emitted = getattr(r, "emitted", None) or []
        late += [t - e for t, e in zip(r.recv, emitted)
                 if rec.in_window(t)]
    p = percentile(late, 99)
    return None if p is None else p * 1e3
