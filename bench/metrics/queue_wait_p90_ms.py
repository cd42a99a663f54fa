"""queue_wait_p90_ms: how long requests waited for a decode slot: 90th
percentile of admission minus arrival over every request due in the
window that reached the server.  Both are the engine's stamps
(``RequestResult.arrival_us``, stamped by ``StreamingServer.submit``,
and ``admitted_us``), as ``Req.arrived`` and ``Req.admitted`` in
window seconds.  A request not admitted when the window closes counts
at its wait so far, as in ``ttft_p90_ms``.  Reads nothing from a record
that carries no such stamps."""

from bench.stats import percentile


def read(rec):
    waits = []
    for r in rec.due_in_window():
        arrived = getattr(r, "arrived", None)
        if arrived is None or arrived > rec.seconds:
            continue
        admitted = getattr(r, "admitted", None)
        if admitted is None or admitted > rec.seconds:
            admitted = rec.seconds
        waits.append(admitted - arrived)
    p = percentile(waits, 90)
    return None if p is None else p * 1e3
