"""host_tick_p99_ms: the host's own share of an engine tick: 99th
percentile, over the window's ticks that dispatched a decode, of the
tick's wall time less the time it spent blocked on the device's tokens
(``engine.last_step["host_s"]``, as ``Step.host_s``).  Under overlapped
decode the device runs the next step meanwhile, so a tick whose host
share exceeds the decode step leaves the device idle.  Reads nothing
from a record that carries no such counter."""

from bench.stats import percentile


def read(rec):
    host = [s.host_s for s in rec.steps
            if s.decoded and rec.in_window(s.t0)
            and getattr(s, "host_s", None) is not None]
    p = percentile(host, 99)
    return None if p is None else p * 1e3
