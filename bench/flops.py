"""Operations and bytes the served work needs, computed from shapes.

Model FLOPs count the multiply-adds of every weight a token multiplies
through (two FLOPs each; of routed experts, only the active ones) for
the tokens computed, the attention products ``QK^T`` and ``PV`` over the
keys each query attends, and the vocabulary projection for the rows
whose logits are used.  The architecture module's ``token_work(conf)``
gives these sizes.  Padding rows, cached tokens and positions past a
row's length are not work the algorithm needs and are not counted.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown device kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def _pairs(start: int, n: int, window: Optional[int]) -> int:
    """(query, key) pairs of ``n`` queries at positions
    ``start..start+n-1``, each attending itself and the keys before it,
    at most ``window`` of them."""
    if window is None:
        return n * start + n * (n + 1) // 2
    return sum(min(p + 1, window) for p in range(start, start + n))


def _prefix_reads(start: int, n: int,
                  window: Optional[int]) -> Tuple[int, int]:
    """Of the ``start`` cached keys before a chunk of ``n`` queries: how
    many the chunk reads, and the (query, cached key) pairs it forms."""
    if window is None:
        return start, n * start
    return (min(start, window - 1),
            sum(min(start, max(0, window - 1 - i)) for i in range(n)))


def forward_flops(work: Dict[str, Any], n_tokens: int,
                  attn_pairs: Callable[[Optional[int]], int],
                  n_logit_rows: int) -> float:
    """FLOPs of a forward pass over ``n_tokens`` tokens with logits for
    ``n_logit_rows`` rows.  ``work`` is the architecture's
    ``token_work(conf)``: the weights a token multiplies through
    (``params``), the head's (``head``) and the attention layers
    (``attention``: layers, heads, head size and window of each kind).
    ``attn_pairs(window)`` is the number of (query, key) pairs the
    tokens' queries see in an attention layer of that window (None:
    unwindowed)."""
    flops = 2.0 * work["params"] * n_tokens
    for a in work["attention"]:
        flops += (4.0 * a["layers"] * a["heads"] * a["head_dim"]
                  * attn_pairs(a["window"]))
    return flops + 2.0 * work["head"] * n_logit_rows


def chunk_flops(work: Dict[str, Any],
                rows: Iterable[Tuple[int, int]]) -> float:
    """A chunk step: each row ``(start, n)`` computes ``n`` prompt tokens
    at positions ``start..start+n-1`` (each attends its prefix and
    itself) and the logits of its last token."""
    flops = 0.0
    for start, n in rows:
        flops += forward_flops(work, n, functools.partial(_pairs, start, n),
                               1)
    return flops


def decode_flops(work: Dict[str, Any], contexts: Iterable[int]) -> float:
    """A decode step: one token per row, attending ``context`` keys."""
    contexts = list(contexts)
    return forward_flops(
        work, len(contexts),
        lambda window: sum(contexts) if window is None
        else sum(min(c, window) for c in contexts), len(contexts))


def prefix_attn_cost(work: Dict[str, Any], rows: Iterable[Tuple[int, int]],
                     kv_bytes: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the chunk step's prefix read needs: for each row
    ``(start, n)`` with a prefix, in every attention layer, its ``n``
    queries against the cached keys and values they see (read once,
    ``kv_bytes`` an element), the queries read in bfloat16 and the flash
    state (output in float32, a running max and sum per query and head)
    written."""
    rows = list(rows)
    total_flops = total_bytes = 0.0
    for a in work["attention"]:
        h, kvh, hd = a["heads"], a["kv_heads"], a["head_dim"]
        flops = bytes_ = 0.0
        for start, n in rows:
            if start <= 0 or n <= 0:
                continue
            keys, pairs = _prefix_reads(start, n, a["window"])
            flops += 4.0 * h * hd * pairs
            bytes_ += (2 * keys * kvh * hd * kv_bytes + n * h * hd * 2
                       + n * h * (hd + 2) * 4)
        total_flops += flops * a["layers"]
        total_bytes += bytes_ * a["layers"]
    return total_flops, total_bytes
