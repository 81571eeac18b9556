"""The Llama architecture (``LlamaForCausalLM``, ``model_type`` "llama").

A decoder of RMSNorm, rotate-half RoPE, grouped-query attention and a
SwiGLU MLP, with no biases and a tied or untied head.  What the
benchmark needs of it, from the configuration file's published keys: the
program's ``ModelConfig``, the served tree's leaves, the work each token
does, and the plain float32 reference's layer, final norm and head.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from bench.reference import attention, mm, rms, rope
from bench.weights import Leaf


def dims(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a forward pass needs, from the published keys."""
    heads = conf["num_attention_heads"]
    return {"layers": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "heads": heads, "kv_heads": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or conf["hidden_size"] // heads,
            "ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
            "theta": float(conf["rope_theta"]),
            "eps": float(conf["rms_norm_eps"]),
            "tied": bool(conf["tie_word_embeddings"])}


def program_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` of the architecture's sizes."""
    from repro.configs.base import ModelConfig
    m = dims(conf)
    return ModelConfig(
        arch_id="llama", family="dense", n_layers=m["layers"], d_model=m["d"],
        n_heads=m["heads"], n_kv_heads=m["kv_heads"], d_ff=m["ff"],
        vocab_size=m["vocab"], head_dim=m["hd"], rope_theta=m["theta"],
        eps=m["eps"], tie_embeddings=m["tied"])


def leaf_shapes(conf: Dict[str, Any]) -> Dict[str, Leaf]:
    """Every leaf of the served tree.  Projections keep the contraction
    last: ``wq`` is (heads, hd, d), ``wo`` is (d, heads, hd)."""
    m = dims(conf)
    n, d, hd, ff = m["layers"], m["d"], m["hd"], m["ff"]
    h, kvh = m["heads"], m["kv_heads"]
    out = {
        "embed": Leaf((m["vocab"], d), True, None, False),
        "final_norm/gamma": Leaf((d,), False, None, False),
        "blocks/norm1/gamma": Leaf((n, d), False, None, True),
        "blocks/norm2/gamma": Leaf((n, d), False, None, True),
        "blocks/attn/wq": Leaf((n, h, hd, d), True, d, True),
        "blocks/attn/wk": Leaf((n, kvh, hd, d), True, d, True),
        "blocks/attn/wv": Leaf((n, kvh, hd, d), True, d, True),
        "blocks/attn/wo": Leaf((n, d, h, hd), True, h * hd, True),
        "blocks/mlp/w1": Leaf((n, ff, d), True, d, True),
        "blocks/mlp/w3": Leaf((n, ff, d), True, d, True),
        "blocks/mlp/w2": Leaf((n, d, ff), True, ff, True),
    }
    if not m["tied"]:
        out["lm_head"] = Leaf((m["vocab"], d), True, d, False)
    return out


def fused_copies(conf: Dict[str, Any]) -> Dict[str, Tuple[str, ...]]:
    """The program's fused decode copies of the served tree, which it
    makes from the leaves above: each holds the rows of these leaves, in
    this order."""
    return {"blocks/attn/wqkv": ("blocks/attn/wq", "blocks/attn/wk",
                                 "blocks/attn/wv"),
            "blocks/attn/wo_f": ("blocks/attn/wo",),
            "blocks/mlp/w13": ("blocks/mlp/w1", "blocks/mlp/w3")}


def token_work(conf: Dict[str, Any]) -> Dict[str, Any]:
    """What one token's forward pass multiplies through: the weights of
    every layer, the head, and the attention layers (see
    ``bench/flops.py``)."""
    m = dims(conf)
    d, hd = m["d"], m["hd"]
    per_layer = d * hd * (2 * m["heads"] + 2 * m["kv_heads"]) + 3 * d * m["ff"]
    return {"params": m["layers"] * per_layer, "head": m["vocab"] * d,
            "attention": [{"layers": m["layers"], "heads": m["heads"],
                           "kv_heads": m["kv_heads"], "head_dim": hd,
                           "window": None}]}


def embed(top: Dict[str, jax.Array], tokens, m: Dict[str, Any]):
    return jnp.take(top["embed"], tokens, axis=0)


def layer(x, w: Dict[str, jax.Array], pos, m: Dict[str, Any], control: bool):
    """One decoder layer over ``x`` (B, T, d) at positions ``pos``."""
    h = rms(x, w["blocks/norm1/gamma"], m["eps"])
    q = mm("btd,hkd->bthk", h, w["blocks/attn/wq"], control)
    k = mm("btd,hkd->bthk", h, w["blocks/attn/wk"], control)
    v = mm("btd,hkd->bthk", h, w["blocks/attn/wv"], control)
    q, k = rope(q, pos, m["theta"]), rope(k, pos, m["theta"])
    a = attention(q, k, v, control)
    x = x + mm("bthk,dhk->btd", a, w["blocks/attn/wo"], control)
    h = rms(x, w["blocks/norm2/gamma"], m["eps"])
    g = mm("btd,fd->btf", h, w["blocks/mlp/w1"], control)
    u = mm("btd,fd->btf", h, w["blocks/mlp/w3"], control)
    return x + mm("btf,df->btd", jax.nn.silu(g) * u, w["blocks/mlp/w2"],
                  control)


def final_norm(x, top: Dict[str, jax.Array], m: Dict[str, Any]):
    return rms(x, top["final_norm/gamma"], m["eps"])


def lm_head(rows, top: Dict[str, jax.Array], m: Dict[str, Any],
            control: bool):
    """Logits of ``rows`` (T, d), after the final norm."""
    head = top["embed"] if m["tied"] else top["lm_head"]
    return mm("td,vd->tv", rows, head, control)
