"""decode_step_ms: device time of one run of the decode program, averaged
over its runs in the traced window.  The decode program is the one in
whose runs the decode-attention kernel ran (the engine's prefill and
decode programs share a name)."""


def read(rec):
    t = rec.trace
    if t is None:
        return None
    runs = [v for k, v in t.modules.items() if k.endswith(" [decode]")]
    n = sum(r for r, _ in runs)
    return 1e3 * sum(s for _, s in runs) / n if n else None
