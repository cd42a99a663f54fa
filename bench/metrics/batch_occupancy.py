"""batch_occupancy: mean share of the decode slots that held a request,
over the engine steps in the window that dispatched a decode
(``engine.active`` after the step, over ``max_slots``)."""


def read(rec):
    ticks = [s.active for s in rec.steps
             if s.decoded and rec.in_window(s.t0)]
    if not ticks:
        return None
    return 100.0 * sum(ticks) / (len(ticks) * rec.max_slots)
