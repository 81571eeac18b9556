"""Model FLOPs of the prompt tokens the chunk steps computed (no padding
rows, no cached tokens; attention over each token's prefix included)
over the chunk step's device time at the chip's bf16 peak."""

from bench import flops


def read(rec):
    secs = rec["trace"]["programs"]["chunk_step"]["seconds"]
    work = sum(flops.chunk_flops(rec["work"], s["chunk"])
               for s in rec["steps"])
    if not secs or not work:
        return None
    peak = flops.peaks(rec["device"]["kind"])["flops_bf16"]
    return 100.0 * work / (secs * peak)
