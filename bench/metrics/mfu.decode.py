"""Model FLOPs of the decoded tokens at their context over the decode
step's device time at the chip's bf16 peak."""

from bench import flops


def read(rec):
    secs = rec["trace"]["programs"]["decode_step"]["seconds"]
    work = sum(flops.decode_flops(rec["work"], s["decode"])
               for s in rec["steps"] if s["decode"])
    if not secs or not work:
        return None
    peak = flops.peaks(rec["device"]["kind"])["flops_bf16"]
    return 100.0 * work / (secs * peak)
