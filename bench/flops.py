"""Operations and bytes the served work needs, computed from shapes.

Model FLOPs count the multiply-adds of every projection (two FLOPs each)
for the tokens computed, the attention products ``QK^T`` and ``PV`` over
the keys each query attends, and the vocabulary projection for the rows
whose logits are used.  Padding rows, cached tokens and positions past a
row's length are not work the algorithm needs and are not counted.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown device kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def layer_matmul_params(cfg) -> int:
    """Weights one token multiplies through in one layer."""
    d, hd = cfg.d_model, cfg.hd()
    attn = d * hd * (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
    return attn + 3 * d * cfg.d_ff


def forward_flops(cfg, n_tokens: int, attn_pairs: int,
                  n_logit_rows: int) -> float:
    """FLOPs of a forward pass over ``n_tokens`` tokens whose queries see
    ``attn_pairs`` (query, key) pairs in all, with logits for
    ``n_logit_rows`` rows."""
    layers = cfg.n_layers
    return (2.0 * layers * layer_matmul_params(cfg) * n_tokens
            + 4.0 * layers * cfg.n_heads * cfg.hd() * attn_pairs
            + 2.0 * cfg.vocab_size * cfg.d_model * n_logit_rows)


def chunk_flops(cfg, rows: Iterable[Tuple[int, int]]) -> float:
    """A chunk step: each row ``(start, n)`` computes ``n`` prompt tokens
    at positions ``start..start+n-1`` (each attends its prefix and
    itself) and the logits of its last token."""
    flops = 0.0
    for start, n in rows:
        flops += forward_flops(cfg, n, n * start + n * (n + 1) // 2, 1)
    return flops


def decode_flops(cfg, contexts: Iterable[int]) -> float:
    """A decode step: one token per row, attending ``context`` keys."""
    contexts = list(contexts)
    return forward_flops(cfg, len(contexts), sum(contexts), len(contexts))


def prefix_attn_cost(cfg, rows: Iterable[Tuple[int, int]],
                     kv_bytes: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the chunk step's prefix read needs: for each row
    ``(start, n)`` with a prefix, in every layer, its ``n`` queries
    against the ``start`` cached keys and values (read once, ``kv_bytes``
    an element), the queries read in bfloat16 and the flash state (output
    in float32, a running max and sum per query and head) written."""
    h, kvh, hd, layers = cfg.n_heads, cfg.n_kv_heads, cfg.hd(), cfg.n_layers
    flops = bytes_ = 0.0
    for start, n in rows:
        if start <= 0 or n <= 0:
            continue
        flops += 4.0 * h * hd * n * start
        bytes_ += (2 * start * kvh * hd * kv_bytes + n * h * hd * 2
                   + n * h * (hd + 2) * 4)
    return flops * layers, bytes_ * layers
