"""The Pallas prefix-read kernel (kernels/paged_prefill_attention.py)
against its roofline: per chunk call, the least time the algorithm
needs (FLOPs of the live prefix at the bf16 peak, or its bytes at the
HBM bandwidth, whichever is longer), summed over the window's calls,
over the kernel's device time in the trace."""

from bench import flops


def read(rec):
    secs = rec["trace"]["kernel_s"]
    if not secs:
        return None
    peak = flops.peaks(rec["device"]["kind"])
    need = 0.0
    for s in rec["steps"]:
        f, b = flops.prefix_attn_cost(rec["work"], s["chunk"], rec["kv_bytes"])
        need += max(f / peak["flops_bf16"], b / peak["hbm_bytes_per_s"])
    if not need:
        return None
    return 100.0 * need / secs
