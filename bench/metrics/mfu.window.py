"""All model FLOPs the window's chunk and decode steps completed over
the traced window at the chip's bf16 peak."""

from bench import flops


def read(rec):
    work = rec["work"]
    done = sum(flops.chunk_flops(work, s["chunk"])
               + (flops.decode_flops(work, s["decode"]) if s["decode"] else 0)
               for s in rec["steps"])
    if not done:
        return None
    peak = flops.peaks(rec["device"]["kind"])["flops_bf16"]
    return 100.0 * done / (rec["trace"]["window_s"] * peak)
