"""The readers of the engine's own stamps and counters (queue wait,
admission to first token, the host's share of a tick, token delivery)
on records made by hand.  The stamps ride on the record's ``Req`` and
``Step`` as ``arrived``, ``admitted``, ``emitted`` and ``host_s``; a
record without them reads nothing."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, peaks  # noqa: E402

READERS = ("queue_wait_p90_ms", "admit_to_first_token_p90_ms",
           "host_tick_p99_ms", "token_delivery_p99_ms")


def _req(uid, due, arrived, admitted, emitted, recv=None):
    recv = [t + 0.001 for t in emitted] if recv is None else recv
    r = harness.Req(uid=uid, prompt=np.zeros(10, np.int32),
                    max_new=max(len(recv), 1), due=due, submitted=arrived,
                    recv=list(recv), tokens=[1] * len(recv),
                    done=bool(recv))
    r.arrived, r.admitted, r.emitted = arrived, admitted, list(emitted)
    return r


def _step(t0, host_s, decoded=True):
    s = harness.Step(t0=t0, t1=t0 + host_s + 0.02, prefills=0,
                     decoded=decoded, active=8)
    s.host_s, s.readback_s = host_s, 0.02
    return s


def _rec(requests, steps=(), seconds=10.0):
    return harness.RunRecord(cell=harness.load_cell("phi4-mini.chat"),
                             seconds=seconds, setup_s=1.5, max_slots=8,
                             peaks=peaks.V5E, requests=list(requests),
                             steps=list(steps), compiles_in_window=0)


def test_queue_wait_counts_unadmitted_requests_at_their_wait():
    # nine wait 100 ms for a slot, then 200 ms to their first token
    reqs = [_req(i, due=i, arrived=i + 0.001, admitted=i + 0.101,
                 emitted=[i + 0.301, i + 0.33]) for i in range(9)]
    rec = _rec(reqs)
    assert harness.reader("queue_wait_p90_ms")(rec) == pytest.approx(100.0)
    assert harness.reader("admit_to_first_token_p90_ms")(rec) == \
        pytest.approx(200.0)
    # arrived at 5 s, not admitted when the 10 s window closes: its wait
    # so far (5 s) is the 90th percentile of ten
    reqs.append(_req(9, due=5.0, arrived=5.0, admitted=None, emitted=[]))
    # admitted after the close: also counted at its wait so far (4 s)
    reqs.append(_req(10, due=6.0, arrived=6.0, admitted=10.4,
                     emitted=[10.6]))
    assert harness.reader("queue_wait_p90_ms")(_rec(reqs)) == \
        pytest.approx(4000.0)
    # due after the close, or never sent: not counted
    reqs += [_req(11, due=10.5, arrived=10.5, admitted=10.6,
                  emitted=[10.9]),
             _req(12, due=9.0, arrived=None, admitted=None, emitted=[])]
    assert harness.reader("queue_wait_p90_ms")(_rec(reqs)) == \
        pytest.approx(4000.0)


def test_admit_to_first_token_counts_admitted_requests_without_a_token():
    reqs = [_req(i, due=i, arrived=i, admitted=i + 0.1,
                 emitted=[i + 0.15]) for i in range(9)]
    # admitted at 9.0 s, its first token after the close: 1 s so far
    reqs.append(_req(9, due=8.5, arrived=8.5, admitted=9.0,
                     emitted=[10.2]))
    assert harness.reader("admit_to_first_token_p90_ms")(_rec(reqs)) == \
        pytest.approx(50.0)
    # a second such request puts its wait at the 90th percentile
    reqs.append(_req(10, due=9.1, arrived=9.1, admitted=9.5, emitted=[]))
    assert harness.reader("admit_to_first_token_p90_ms")(_rec(reqs)) == \
        pytest.approx(500.0)
    # not admitted by the close: no service time to count
    reqs.append(_req(11, due=9.2, arrived=9.2, admitted=None, emitted=[]))
    assert harness.reader("admit_to_first_token_p90_ms")(_rec(reqs)) == \
        pytest.approx(500.0)


def test_host_tick_takes_decoding_ticks_in_the_window():
    steps = [_step(0.01 * k, 0.002) for k in range(1, 100)]
    steps.append(_step(5.0, 0.009))                 # the slowest in 100
    steps.append(_step(5.1, 0.5, decoded=False))    # prefill only
    steps.append(_step(10.5, 0.8))                  # after the close
    rec = _rec([], steps)
    assert harness.reader("host_tick_p99_ms")(rec) == pytest.approx(2.0)
    steps.append(_step(6.0, 0.010))
    assert harness.reader("host_tick_p99_ms")(_rec([], steps)) == \
        pytest.approx(9.0)


def test_token_delivery_takes_tokens_received_in_the_window():
    emitted = [1.0 + 0.03 * k for k in range(100)]
    recv = [t + 0.001 for t in emitted]
    recv[50] += 0.004                               # 5 ms late
    reqs = [_req(0, 0.0, 0.0, 0.1, emitted, recv),
            # emitted before the close, received after it
            _req(1, 0.0, 0.0, 0.1, [9.99], [10.3])]
    assert harness.reader("token_delivery_p99_ms")(_rec(reqs)) == \
        pytest.approx(1.0)
    reqs[1] = _req(1, 0.0, 0.0, 0.1, [9.99], [9.999])
    assert harness.reader("token_delivery_p99_ms")(_rec(reqs)) == \
        pytest.approx(5.0)


@pytest.mark.parametrize("name", READERS)
def test_a_record_without_the_stamps_reads_nothing(name):
    reqs = [harness.Req(uid=0, prompt=np.zeros(10, np.int32), max_new=2,
                        due=0.0, submitted=0.0, recv=[0.5, 0.6],
                        tokens=[1, 1], done=True)]
    steps = [harness.Step(t0=0.5, t1=0.53, prefills=0, decoded=True,
                          active=1)]
    assert harness.reader(name)(_rec(reqs, steps)) is None
