"""decode_attn_roofline: the decode-attention kernel's share of its
roofline over the traced window: the least time the chip needs for the
attention of every token the window decoded (the K and V of its live
positions plus its q and output at 819 GB/s, or its FLOPs at peak,
whichever is longer, as the configuration's family counts them), over
the kernel's device time in the trace.  Bytes follow live lengths, never the cache's capacity or
the tiles a kernel fetches."""

import re

from bench import harness, work


def read(rec):
    t = rec.trace
    if t is None:
        return None
    kernel_s = sum(s for name, s in t.ops.items()
                   if re.search(harness.DECODE_KERNEL, name))
    if kernel_s <= 0:
        return None
    m, pk, fam = rec.dims, rec.peaks, rec.family
    need = 0.0
    for r in rec.requests:
        for k, x in enumerate(r.recv):
            if rec.trace_t0 <= x <= rec.trace_t1:
                live = r.prompt_len + k
                need += work.roofline_s(fam.attention_flops(m, live),
                                        fam.decode_attn_bytes(m, live),
                                        pk.bf16_flops, pk.hbm_bw)
    return 100.0 * need / kernel_s if need else None
