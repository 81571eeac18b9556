"""All model FLOPs the window's chunk and decode steps completed over
the traced window at the chip's bf16 peak."""

from bench import flops


def read(rec):
    cfg = rec["cfg"]
    work = sum(flops.chunk_flops(cfg, s["chunk"])
               + (flops.decode_flops(cfg, s["decode"]) if s["decode"] else 0)
               for s in rec["steps"])
    if not work:
        return None
    peak = flops.peaks(rec["device"]["kind"])["flops_bf16"]
    return 100.0 * work / (rec["trace"]["window_s"] * peak)
