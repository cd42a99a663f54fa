"""collective_share: the share of the chips' busy time spent in
cross-chip collectives over the traced window: the device seconds of
the operations whose HLO names begin ``all-reduce``, ``all-gather``,
``reduce-scatter``, ``all-to-all`` or ``collective-permute`` (their
``-start`` / ``-done`` halves included), summed over the chips, over
the chips' busy seconds.  Reads nothing from a trace with no such
operation (one chip, or a program with no collective)."""

import re

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")


def read(rec):
    t = rec.trace
    if t is None or t.busy_s <= 0:
        return None
    coll = sum(s for name, s in t.ops.items() if COLLECTIVE.match(name))
    if coll <= 0:
        return None
    return 100.0 * coll / (t.busy_s * t.devices)
