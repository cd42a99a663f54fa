"""prefill_step_share: share of the traced window the device spent in
the prefill program: runs of the program that shares the decode
program's name but ran no decode-attention kernel.  With overlapped
decode a prefill's device time does not sit inside the host span of
the step that dispatched it, so the share is read from the trace."""


def read(rec):
    t = rec.trace
    if t is None or t.window_s <= 0:
        return None
    base = {k[:-len(" [decode]")] for k in t.modules
            if k.endswith(" [decode]")}
    if not base:
        return None
    s = sum(v[1] for k, v in t.modules.items() if k in base)
    return 100.0 * s / t.window_s
