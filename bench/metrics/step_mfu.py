"""step_mfu: the whole step's share of the chips' peak over the traced
window: model FLOPs of the prefills and decoded tokens the window
served, over (window seconds x chips x peak bf16 FLOP/s).  A prefill
counts its prompt's tokens (not its padded bucket) and is placed where
its request's first token was received; a decoded token counts every
layer's matmuls, attention over its live positions, and the head."""

def read(rec):
    t = rec.trace
    if t is None or t.window_s <= 0:
        return None
    m, lo, hi, fam = rec.dims, rec.trace_t0, rec.trace_t1, rec.family
    flops = 0
    for r in rec.requests:
        for k, x in enumerate(r.recv):
            if lo <= x <= hi:
                flops += fam.decode_token_flops(m, r.prompt_len + k)
                if k == 0:
                    flops += fam.prefill_flops(m, r.prompt_len - 1)
    if not flops:
        return None
    chips = t.devices
    return 100.0 * flops / (t.window_s * chips * rec.peaks.bf16_flops)
