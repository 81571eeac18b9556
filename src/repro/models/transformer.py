"""Decoder-only LM covering dense / MoE / SSM / hybrid / VLM families.

One functional model, driven entirely by ``ModelConfig``:

  * params are *stacked per layer* and iterated with ``lax.scan`` — the HLO
    stays one-block-sized regardless of depth (critical for the 512-device
    dry-run compiles on this 1-CPU container, and for TPU compile times),
  * every weight leaf may be a float array (training) or a
    ``QuantizedTensor`` (post-training-quantized serving) — ``qdot``
    dispatches, so the paper's PTQ flow reuses the same forward code,
  * decode keeps a KV cache that is optionally Q8_0-quantized per
    (position, kv-head) — the beyond-paper extension that matters at 32k+.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig
from repro.core.qlinear import qdot, qeinsum
from repro.core.quantization import (QuantizedTensor, qt_concat,
                                     quantize_rows,
                                     qt_fold_lead_into_groups,
                                     qt_reshape_lead)
from repro.models import layers as L
from repro.models import ssm as S

Params = Any
Cache = Dict[str, Any]


def _cdt(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_norm(cfg: ModelConfig):
    p = {"gamma": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.norm_type == "layernorm":
        p["beta"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


def _init_attn(key, cfg: ModelConfig):
    """Head-structured weights: (H, hd, D) / (D, H, hd).

    Keeping the head axis explicit lets the `model` mesh axis shard heads
    directly (GSPMD pads non-divisible head counts) instead of resharding a
    flat H*hd dim whose shard boundaries cut through heads.
    """
    hd = cfg.hd()
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = _pdt(cfg)
    sc = 1.0 / (cfg.d_model ** 0.5)
    so = 1.0 / ((cfg.n_heads * hd) ** 0.5)
    return {
        "wq": (jax.random.normal(k1, (cfg.n_heads, hd, cfg.d_model)) * sc).astype(dt),
        "wk": (jax.random.normal(k2, (cfg.n_kv_heads, hd, cfg.d_model)) * sc).astype(dt),
        "wv": (jax.random.normal(k3, (cfg.n_kv_heads, hd, cfg.d_model)) * sc).astype(dt),
        "wo": (jax.random.normal(k4, (cfg.d_model, cfg.n_heads, hd)) * so).astype(dt),
    }


def _init_mlp(key, cfg: ModelConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    dt = _pdt(cfg)
    if cfg.mlp_type == "gelu":
        return {"w1": L.dense_init(k1, cfg.d_ff, cfg.d_model, dt),
                "w2": L.dense_init(k2, cfg.d_model, cfg.d_ff, dt)}
    return {"w1": L.dense_init(k1, cfg.d_ff, cfg.d_model, dt),
            "w3": L.dense_init(k3, cfg.d_ff, cfg.d_model, dt),
            "w2": L.dense_init(k2, cfg.d_model, cfg.d_ff, dt)}


def _init_moe(key, cfg: ModelConfig):
    k0, k1, k2, k3 = jax.random.split(key, 4)
    dt = _pdt(cfg)
    e, f, d = cfg.n_experts, cfg.d_ff, cfg.d_model
    scale = 1.0 / (d ** 0.5)
    return {
        "router": (jax.random.normal(k0, (e, d)) * scale).astype(jnp.float32),
        "w1": (jax.random.normal(k1, (e, f, d)) * scale).astype(dt),
        "w3": (jax.random.normal(k3, (e, f, d)) * scale).astype(dt),
        "w2": (jax.random.normal(k2, (e, d, f)) * (1.0 / f ** 0.5)).astype(dt),
    }


def _init_dense_block(key, cfg: ModelConfig, moe: bool):
    k1, k2 = jax.random.split(key)
    blk = {"norm1": _init_norm(cfg), "attn": _init_attn(k1, cfg),
           "norm2": _init_norm(cfg)}
    if moe:
        blk["moe"] = _init_moe(k2, cfg)
    else:
        blk["mlp"] = _init_mlp(k2, cfg)
    return blk


def _ssm_dims(cfg: ModelConfig) -> S.SSMDims:
    return S.make_ssm_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_expand,
                           cfg.ssm_head_dim, cfg.ssm_groups, cfg.conv_width)


def _init_ssm_block(key, cfg: ModelConfig):
    return {"norm1": _init_norm(cfg),
            "ssm": S.init_mamba2_params(key, _ssm_dims(cfg), _pdt(cfg))}


def init_params(cfg: ModelConfig, key) -> Params:
    kemb, kblocks, khead, kattn = jax.random.split(key, 4)
    dt = _pdt(cfg)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(kemb, (cfg.padded_vocab(), cfg.d_model))
                  * 0.02).astype(dt),
        "final_norm": _init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(khead, cfg.padded_vocab(),
                                         cfg.d_model, dt)

    fam = cfg.family
    if fam == "moe" and cfg.moe_every > 1:
        # llama4-style interleave: pattern = [dense x (k-1), moe], repeated.
        n_pat = cfg.n_layers // cfg.moe_every
        kd, km = jax.random.split(kblocks)
        dkeys = jax.random.split(kd, n_pat * (cfg.moe_every - 1))
        dkeys = dkeys.reshape((n_pat, cfg.moe_every - 1) + dkeys.shape[1:])
        mkeys = jax.random.split(km, n_pat)
        params["blocks_dense"] = jax.vmap(jax.vmap(
            lambda k: _init_dense_block(k, cfg, moe=False)))(dkeys)
        params["blocks_moe"] = jax.vmap(
            lambda k: _init_dense_block(k, cfg, moe=True))(mkeys)
    elif fam in ("dense", "vlm", "moe"):
        keys = jax.random.split(kblocks, cfg.n_layers)
        params["blocks"] = jax.vmap(
            lambda k: _init_dense_block(k, cfg, moe=(fam == "moe")))(keys)
    elif fam == "ssm":
        keys = jax.random.split(kblocks, cfg.n_layers)
        params["blocks"] = jax.vmap(
            lambda k: _init_ssm_block(k, cfg))(keys)
    elif fam == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        n_main = n_super * cfg.attn_every
        keys = jax.random.split(kblocks, cfg.n_layers)
        all_blocks = jax.vmap(lambda k: _init_ssm_block(k, cfg))(keys)
        params["blocks_main"] = jax.tree_util.tree_map(
            lambda x: x[:n_main].reshape(n_super, cfg.attn_every, *x.shape[1:]),
            all_blocks)
        params["blocks_tail"] = jax.tree_util.tree_map(
            lambda x: x[n_main:], all_blocks)
        params["shared_attn"] = _init_dense_block(kattn, cfg, moe=False)
    else:
        raise ValueError(f"family {fam} not built here (audio -> encdec.py)")
    return params


# ---------------------------------------------------------------------------
# decode-weight fusion (7 GEMVs/layer -> 4)
# ---------------------------------------------------------------------------


def _merge_head_axes(w):
    """(*lead, H, hd, D) -> (*lead, H*hd, D); works on float or quantized."""
    if isinstance(w, QuantizedTensor):
        *lead, h, hd, _ = w.q.shape
        return qt_reshape_lead(w, *lead, h * hd)
    *lead, h, hd, d = w.shape
    return w.reshape(*lead, h * hd, d)


def _fold_head_axes(w):
    """(*lead, D, H, hd) -> (*lead, D, H*hd); works on float or quantized."""
    if isinstance(w, QuantizedTensor):
        return qt_fold_lead_into_groups(w)
    *lead, d, h, hd = w.shape
    return w.reshape(*lead, d, h * hd)


def _concat_rows(ws):
    if isinstance(ws[0], QuantizedTensor):
        return qt_concat(ws, axis=-2)
    return jnp.concatenate(ws, axis=-2)


def fuse_decode_weights(params: Params, cfg: ModelConfig) -> Params:
    """Add fused decode-GEMV operands next to the per-projection weights.

    Single-token decode is HBM-bandwidth- and launch-bound: each layer runs
    7 independent quantized GEMVs (q/k/v/o + gate/up/down), each streaming
    its weight through its own kernel call and — on the integer/pallas
    strategies — re-quantizing the same activation vector.  Fusing

        wqkv = [wq; wk; wv]  ->  ((H + 2*KVH) * hd, D)
        w13  = [w1; w3]      ->  (2 * d_ff, D)
        wo_f = wo flattened  ->  (D, H * hd)

    drops that to 4 launches and quantizes the post-norm activation once
    per fused projection.  Codes/scales are concatenated structurally
    (core.quantization qt_*), never requantized, so fused and unfused
    forward passes agree to f32 summation order.

    The walk is structural: any subtree carrying {wq, wk, wv, wo} (stacked
    per layer or not) gains ``wqkv``/``wo_f``; any plain SwiGLU mlp subtree
    gains ``w13``.  MoE expert banks (which also hold w1/w3/w2 but route
    through einsum dispatch) are left alone.  The per-projection weights
    are kept — prefill still consumes the head-structured layout; a
    production build would derive one from the other at load time.
    """

    def fusable(ws):
        """All-quantized or all-float; a min_size policy can mix kinds
        (e.g. float wk beside quantized wq) — skip fusion there."""
        kinds = {isinstance(w, QuantizedTensor) for w in ws}
        if len(kinds) > 1:
            return False
        if kinds == {True} and len({(w.group_size, w.bits) for w in ws}) > 1:
            return False
        return True

    def walk(d):
        if not isinstance(d, dict):
            return d
        out = {k: walk(v) for k, v in d.items()}
        if ({"wq", "wk", "wv", "wo"} <= set(out)
                and fusable([out["wq"], out["wk"], out["wv"]])):
            out["wqkv"] = _concat_rows([_merge_head_axes(out["wq"]),
                                        _merge_head_axes(out["wk"]),
                                        _merge_head_axes(out["wv"])])
            out["wo_f"] = _fold_head_axes(out["wo"])
        if ({"w1", "w3", "w2"} <= set(out) and "router" not in out
                and fusable([out["w1"], out["w3"]])):
            out["w13"] = _concat_rows([out["w1"], out["w3"]])
        return out

    return walk(params)


# ---------------------------------------------------------------------------
# rope helpers
# ---------------------------------------------------------------------------


def _rope_cos_sin(cfg: ModelConfig, positions: jax.Array):
    """positions: (B, S) for rope, (3, B, S) for mrope; None for 'none'."""
    if cfg.rope_type == "none":
        return None
    hd = cfg.hd()
    if cfg.rope_type == "mrope":
        cos, sin = L.mrope_angles(positions, hd, cfg.rope_theta,
                                  tuple(cfg.mrope_sections))
    else:
        cos, sin = L.rope_angles(positions, hd, cfg.rope_theta)
    return cos, sin


# ---------------------------------------------------------------------------
# blocks — full-sequence (train / prefill)
# ---------------------------------------------------------------------------


def _attn_seq(p, x, cfg: ModelConfig, rope_cs, *, causal=True,
              return_kv=False):
    """x (B, S, D) -> (out, (k, v))."""
    b, s, _ = x.shape
    hd = cfg.hd()
    h = L.apply_norm(x, p["norm1"], cfg.norm_type, cfg.eps)
    q = qeinsum("bsd,hkd->bshk", h, p["attn"]["wq"])
    k = qeinsum("bsd,hkd->bshk", h, p["attn"]["wk"])
    v = qeinsum("bsd,hkd->bshk", h, p["attn"]["wv"])
    if rope_cs is not None:
        cos, sin = rope_cs                                  # (B, S, hd)
        q = L.apply_rope(q, cos[:, :, None], sin[:, :, None])
        k = L.apply_rope(k, cos[:, :, None], sin[:, :, None])
    q = q * (hd ** -0.5)
    acfg = L.AttnConfig(cfg.n_heads, cfg.n_kv_heads, hd, causal=causal,
                        q_chunk=cfg.q_chunk)
    out = L.attention_scores_blockwise(q, k, v, acfg)
    out = qeinsum("bshk,dhk->bsd", out, p["attn"]["wo"])
    return out.astype(x.dtype), ((k, v) if return_kv else None)


def _mlp_or_moe(p, x, cfg: ModelConfig, decode: bool = False):
    h = L.apply_norm(x, p["norm2"], cfg.norm_type, cfg.eps)
    if "moe" in p:
        return L.moe_mlp(p["moe"], h, n_experts=cfg.n_experts,
                         top_k=cfg.top_k, group_size=cfg.moe_group,
                         capacity_factor=cfg.capacity_factor,
                         dense_dispatch=decode).astype(x.dtype)
    if cfg.mlp_type == "gelu":
        return L.gelu_mlp(p["mlp"], h)
    return L.swiglu_mlp(p["mlp"], h)


def _dense_block_seq(p, x, cfg: ModelConfig, rope_cs, causal=True,
                     return_kv=False):
    a, kv = _attn_seq(p, x, cfg, rope_cs, causal=causal, return_kv=return_kv)
    x = x + a
    x = x + _mlp_or_moe(p, x, cfg)
    return x, kv


def _ssm_block_seq(p, x, cfg: ModelConfig, conv_state=None, ssm_state=None):
    h = L.apply_norm(x, p["norm1"], cfg.norm_type, cfg.eps)
    y, (new_conv, new_ssm) = S.mamba2_forward(
        p["ssm"], h, _ssm_dims(cfg), cfg.ssm_chunk, conv_state, ssm_state)
    return x + y, (new_conv, new_ssm)


# ---------------------------------------------------------------------------
# backbone — full sequence
# ---------------------------------------------------------------------------


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "block":
        return jax.checkpoint(fn, prevent_cse=False)
    if cfg.remat == "dots":
        # selective: keep matmul outputs, recompute elementwise — trades
        # ~(B,S,D)-sized residuals per matmul for skipping the recompute
        # of every projection in the backward pass (§Perf lever for
        # compute-dominant train cells)
        return jax.checkpoint(
            fn, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def forward_hidden(params: Params, cfg: ModelConfig, x: jax.Array,
                   positions: jax.Array, collect_cache: bool = False):
    """x: (B, S, D) input embeddings -> (hidden (B,S,D), cache_parts)."""
    rope_cs = _rope_cos_sin(cfg, positions)
    fam = cfg.family

    if fam == "moe" and cfg.moe_every > 1:
        def one(h, lp):
            h2, kv = _dense_block_seq(lp, h, cfg, rope_cs,
                                      return_kv=collect_cache)
            return h2, kv
        one = _maybe_remat(one, cfg)

        def pat_body(h, lps):
            lp_dense, lp_moe = lps
            h, kvd = lax.scan(one, h, lp_dense)
            h, kvm = one(h, lp_moe)
            return h, (kvd, kvm)
        x, cache = lax.scan(pat_body, x,
                            (params["blocks_dense"], params["blocks_moe"]))

    elif fam in ("dense", "vlm", "moe"):
        def body(h, lp):
            h2, kv = _dense_block_seq(lp, h, cfg, rope_cs,
                                      return_kv=collect_cache)
            return h2, kv
        body = _maybe_remat(body, cfg)
        x, kvs = lax.scan(body, x, params["blocks"])
        cache = kvs                                  # ((L,B,S,KVH,hd) x2) | None

    elif fam == "ssm":
        def body(h, lp):
            h2, (cs, ss) = _ssm_block_seq(lp, h, cfg)
            return h2, (cs, ss) if collect_cache else None
        body = _maybe_remat(body, cfg)
        x, cache = lax.scan(body, x, params["blocks"])

    elif fam == "hybrid":
        def inner(h, lp):
            h2, st = _ssm_block_seq(lp, h, cfg)
            return h2, st if collect_cache else None
        inner = _maybe_remat(inner, cfg)
        shared = params["shared_attn"]

        def super_body(h, lp_super):
            h, ssm_sts = lax.scan(inner, h, lp_super)
            h, kv = _dense_block_seq(shared, h, cfg, rope_cs,
                                     return_kv=collect_cache)
            return h, (ssm_sts, kv)
        super_body = _maybe_remat(super_body, cfg)
        x, (main_sts, attn_kvs) = lax.scan(super_body, x,
                                           params["blocks_main"])
        x, tail_sts = lax.scan(inner, x, params["blocks_tail"])
        cache = (main_sts, attn_kvs, tail_sts)
    else:
        raise ValueError(fam)

    x = L.apply_norm(x, params["final_norm"], cfg.norm_type, cfg.eps)
    return x, cache


def _head_weight(params: Params, cfg: ModelConfig):
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


def embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, Any]
                 ) -> jax.Array:
    """tokens -> embeddings; VLM/audio batches carry precomputed embeds
    (the modality frontend is a stub per the assignment)."""
    if "embeds" in batch:
        return batch["embeds"].astype(_cdt(cfg))
    return L.embed_lookup(params["embed"], batch["tokens"]).astype(_cdt(cfg))


def _default_positions(cfg: ModelConfig, b: int, s: int, batch):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.rope_type == "mrope":
        pos = jnp.broadcast_to(pos, (3, b, s))
    return pos


# ---------------------------------------------------------------------------
# loss (chunked cross-entropy — logits never materialize at (B,S,V))
# ---------------------------------------------------------------------------


def lm_loss(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            chunk: int = 512) -> jax.Array:
    tokens_in = batch.get("tokens")
    labels = batch["labels"]                        # (B, S) int32
    b, s = labels.shape
    positions = _default_positions(cfg, b, s, batch)
    x = embed_inputs(params, cfg, batch)
    hidden, _ = forward_hidden(params, cfg, x, positions)
    w = _head_weight(params, cfg)

    c = min(chunk, s)
    while s % c:
        c -= 1
    hs = hidden.reshape(b, s // c, c, cfg.d_model)
    ls = labels.reshape(b, s // c, c)

    def ce_chunk(carry, inp):
        h, y = inp                                  # (B,c,D), (B,c)
        logits = L.lm_head(w, h)                    # (B,c,V) f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - tgt), None

    ce_chunk = _maybe_remat(ce_chunk, cfg)
    total, _ = lax.scan(ce_chunk, jnp.zeros((), jnp.float32),
                        (jnp.moveaxis(hs, 1, 0), jnp.moveaxis(ls, 1, 0)))
    return total / (b * s)


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def _kv_int8(cfg: ModelConfig) -> bool:
    return cfg.kv_cache_dtype == "int8"


def _quantize_kv(vec: jax.Array):
    """vec (..., hd) -> int8 codes + one f32 scale per vector (group=hd)."""
    return quantize_rows(vec)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Cache:
    hd = cfg.hd() if cfg.n_heads else 0      # SSM family: no attention
    kvd = jnp.int8 if _kv_int8(cfg) else _cdt(cfg)
    cache: Cache = {"lens": jnp.zeros((batch,), jnp.int32)}

    def attn_cache(n_layers):
        c = {"k": jnp.zeros((n_layers, batch, max_seq, cfg.n_kv_heads, hd), kvd),
             "v": jnp.zeros((n_layers, batch, max_seq, cfg.n_kv_heads, hd), kvd)}
        if _kv_int8(cfg):
            c["ks"] = jnp.zeros((n_layers, batch, max_seq, cfg.n_kv_heads),
                                jnp.float32)
            c["vs"] = jnp.zeros_like(c["ks"])
        return c

    def ssm_cache(n_layers):
        d = _ssm_dims(cfg)
        gn = d.n_groups * d.state
        w1 = cfg.conv_width - 1
        conv = (jnp.zeros((n_layers, batch, w1, d.d_inner), jnp.float32),
                jnp.zeros((n_layers, batch, w1, gn), jnp.float32),
                jnp.zeros((n_layers, batch, w1, gn), jnp.float32))
        return {"conv": conv,
                "state": jnp.zeros((n_layers, batch, d.n_heads, d.head_dim,
                                    d.state), jnp.float32)}

    fam = cfg.family
    if fam == "moe" and cfg.moe_every > 1:
        n_pat = cfg.n_layers // cfg.moe_every
        full = attn_cache(cfg.n_layers)
        cache["attn_dense"] = jax.tree_util.tree_map(
            lambda x: x[: n_pat * (cfg.moe_every - 1)].reshape(
                n_pat, cfg.moe_every - 1, *x.shape[1:]), full)
        cache["attn_moe"] = attn_cache(n_pat)
    elif fam in ("dense", "vlm", "moe"):
        cache["attn"] = attn_cache(cfg.n_layers)
    elif fam == "ssm":
        cache["ssm"] = ssm_cache(cfg.n_layers)
    elif fam == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        n_main = n_super * cfg.attn_every
        cache["ssm_main"] = jax.tree_util.tree_map(
            lambda x: x.reshape(n_super, cfg.attn_every, *x.shape[1:]),
            ssm_cache(n_main))
        cache["ssm_tail"] = ssm_cache(cfg.n_layers - n_main)
        cache["attn"] = attn_cache(n_super)
    return cache


def _store_kv(cache_layer, k, v, pos, int8: bool):
    """Write (B, KVH, hd) new k/v at per-row positions into (B,S,KVH,hd)."""
    if int8:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        upd = {"k": kq, "v": vq, "ks": ks, "vs": vs}
    else:
        upd = {"k": k.astype(cache_layer["k"].dtype),
               "v": v.astype(cache_layer["v"].dtype)}

    def write(buf, new):
        # buf (B, S, ...), new (B, ...) -> write at pos[b] per row
        return jax.vmap(
            lambda bb, nn, pp: lax.dynamic_update_slice_in_dim(
                bb, nn[None], pp, axis=0))(buf, new, pos)

    return {kk: write(cache_layer[kk], upd[kk]) if kk in upd else cache_layer[kk]
            for kk in cache_layer}


def _decode_qkv(p_attn, h, cfg: ModelConfig):
    """Post-norm hidden (B, D) -> q (B, H, hd), k/v (B, KVH, hd).

    With fused weights (fuse_decode_weights) this is ONE quantized GEMV
    against ``wqkv`` instead of three — the activation vector is read (and,
    on the integer/pallas strategies, quantized) once.
    """
    b = h.shape[0]
    hd, nh, kvh = cfg.hd(), cfg.n_heads, cfg.n_kv_heads
    if "wqkv" in p_attn:
        qkv = qdot(h, p_attn["wqkv"]).astype(h.dtype)   # (B, (H+2KVH)*hd)
        q, k, v = jnp.split(qkv, [nh * hd, (nh + kvh) * hd], axis=-1)
        return (q.reshape(b, nh, hd), k.reshape(b, kvh, hd),
                v.reshape(b, kvh, hd))
    q = qeinsum("bd,hkd->bhk", h, p_attn["wq"])
    k = qeinsum("bd,hkd->bhk", h, p_attn["wk"])
    v = qeinsum("bd,hkd->bhk", h, p_attn["wv"])
    return q, k, v


def _decode_out_proj(p_attn, out, x_dtype):
    """Attention output (B, H, hd) -> residual (B, D) via wo (fused: one
    flat GEMV against ``wo_f``)."""
    b, nh, hd = out.shape
    if "wo_f" in p_attn:
        return qdot(out.reshape(b, nh * hd), p_attn["wo_f"]).astype(x_dtype)
    return qeinsum("bhk,dhk->bd", out, p_attn["wo"]).astype(x_dtype)


def _attn_decode_layer(p, x, cfg: ModelConfig, lcache, pos, rope_cs):
    """x (B, D) single position; lcache holds (B,S,KVH,hd) buffers."""
    hd = cfg.hd()
    int8 = _kv_int8(cfg)
    h = L.apply_norm(x, p["norm1"], cfg.norm_type, cfg.eps)
    q, k, v = _decode_qkv(p["attn"], h, cfg)
    if rope_cs is not None:
        cos, sin = rope_cs                                   # (B, hd)
        q = L.apply_rope(q, cos[:, None], sin[:, None])
        k = L.apply_rope(k, cos[:, None], sin[:, None])
    lcache = _store_kv(lcache, k, v, pos, int8)
    acfg = L.AttnConfig(cfg.n_heads, cfg.n_kv_heads, hd)
    out = L.attention_decode(
        q * (hd ** -0.5), lcache["k"], lcache["v"], pos + 1, acfg,
        lcache.get("ks"), lcache.get("vs"))
    x = x + _decode_out_proj(p["attn"], out, x.dtype)
    x = x + _mlp_or_moe(p, x[:, None, :], cfg, decode=True)[:, 0]
    return x, lcache


def supports_paged_cache(cfg: ModelConfig) -> bool:
    """Paged decode covers the families whose cache is one stacked attn
    bank (dense / vlm / non-interleaved moe); ssm/hybrid state and the
    llama4-style interleave keep the dense per-slot reservation."""
    return (cfg.family in ("dense", "vlm", "moe") and cfg.moe_every <= 1
            and cfg.n_heads > 0)


def init_paged_cache(cfg: ModelConfig, batch: int, *, block_size: int = 64,
                     n_blocks: int, max_blocks_per_seq: int) -> Cache:
    """Block-pool KV cache + page table (serving/paged_cache.py layout).

    Unlike :func:`init_cache`, HBM here is ``n_blocks * block_size`` rows
    total, shared by all slots through ``page_table`` — a slot owns only
    the blocks its live length needs (allocator is host-side, in the
    engine).  ``page_table`` rows are -1 where unassigned."""
    if not supports_paged_cache(cfg):
        raise ValueError(f"paged cache unsupported for family {cfg.family}")
    hd = cfg.hd()
    kvd = jnp.int8 if _kv_int8(cfg) else _cdt(cfg)
    pool_shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads, hd)
    attn = {"k": jnp.zeros(pool_shape, kvd), "v": jnp.zeros(pool_shape, kvd)}
    if _kv_int8(cfg):
        attn["ks"] = jnp.zeros(pool_shape[:-1], jnp.float32)
        attn["vs"] = jnp.zeros_like(attn["ks"])
    return {"lens": jnp.zeros((batch,), jnp.int32),
            "page_table": jnp.full((batch, max_blocks_per_seq), -1,
                                   jnp.int32),
            "attn": attn}


def _serve_mesh_helpers(cfg: ModelConfig, mesh):
    """with_sharding_constraint helpers for the storage-sharded /
    compute-replicated tensor-parallel serving scheme (mesh=None ->
    identity fns, zero cost on the single-device path).

    The scheme: the paged KV pool shards its KV-heads dim over `model`
    (per-head attention math is local — heads only mix at the wo
    contraction), weights are *stored* sharded (serve-mode param specs)
    but constrained replicated at use, and the attention output is
    constrained replicated before the wo contraction.  Every collective
    this induces is an all-gather — pure data movement, never
    arithmetic — so sharded streams stay bit-identical to unsharded
    ones (the head_dim contraction itself is never split, keeping every
    floating-point reduction in single-device summation order).

    Returns ``(crep, cpool)``: ``crep(tree)`` constrains every array
    leaf replicated; ``cpool(attn, lead)`` pins pool buffers' KV-heads
    dim to `model`, where ``lead`` counts leading unsharded dims (3 for
    the stacked (L, NB, BS, KVH, hd) pool, 2 for a per-layer slice
    inside the scan)."""
    if mesh is None:
        return (lambda t: t), (lambda attn, lead=3: attn)
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.distribution.sharding import pool_model_axis
    rep = NamedSharding(mesh, PartitionSpec())
    kvh_ax = pool_model_axis(cfg, mesh)

    def crep(t):
        return jax.tree_util.tree_map(
            lambda a: lax.with_sharding_constraint(a, rep), t)

    def cpool(attn, lead=3):
        out = {}
        for kk, buf in attn.items():
            pad = [None] * lead
            spec = (PartitionSpec(*pad, kvh_ax, None) if kk in ("k", "v")
                    else PartitionSpec(*pad, kvh_ax))
            out[kk] = lax.with_sharding_constraint(
                buf, NamedSharding(mesh, spec))
        return out

    return crep, cpool


def _paged_decode_attend(cfg: ModelConfig, mode: str, mesh=None):
    """The decode step's attention over one layer's block pool:
    ``attend(q, lcache, pt, length) -> (B, H, hd)`` in ``q``'s dtype.

    Mode "oracle" (see :func:`prefill_fused_mode`) gathers each row's
    whole page-table span and runs ``layers.attention_decode`` over it
    (``layers.paged_attention_decode``).  Modes "kernel"/"interpret" run
    the Pallas kernel (`kernels.paged_decode_attention`), which reads each
    row's live pages through the page table in its index_map and keeps
    the softmax in VMEM.  Under a mesh the kernel runs under
    ``shard_map`` with the pool's own KV-heads spec: attention per head is
    local, so each device reads only its heads' pages, and where
    ``pool_model_axis`` replicates the pool the kernel runs replicated."""
    if mode == "oracle":
        acfg = L.AttnConfig(cfg.n_heads, cfg.n_kv_heads, cfg.hd())

        def attend(q, lc, pt, length):
            # named scopes kv_gather and attend are inside
            return L.paged_attention_decode(q, lc["k"], lc["v"], pt,
                                            length, acfg, lc.get("ks"),
                                            lc.get("vs"))
        return attend

    from repro.kernels import ops as KO
    kernel = functools.partial(KO.paged_decode_attention,
                               interpret=(mode == "interpret"))
    int8 = _kv_int8(cfg)
    if mesh is not None:
        from repro.distribution.sharding import pool_model_axis
        ax = pool_model_axis(cfg, mesh)
        heads = PartitionSpec(None, ax, None)            # q, out: (B, H, hd)
        pool = PartitionSpec(None, None, ax, None)       # (NB, BS, KVH, hd)
        scale = PartitionSpec(None, None, ax)            # (NB, BS, KVH)
        kernel = jax.shard_map(
            kernel, mesh=mesh,
            in_specs=(heads, pool, pool, PartitionSpec(), PartitionSpec())
            + ((scale, scale) if int8 else ()),
            out_specs=heads, check_vma=False)

    def attend(q, lc, pt, length):
        with jax.named_scope("attend"):
            scales = (lc["ks"], lc["vs"]) if int8 else ()
            out = kernel(q, lc["k"], lc["v"], pt, length, *scales)
            return out.astype(q.dtype)
    return attend


def _attn_decode_layer_paged(p, x, cfg: ModelConfig, lcache, pt, pos,
                             rope_cs, attend, crep=None, cpool=None):
    """One decode layer against the block pool.

    lcache: {"k"/"v": (NB, BS, KVH, hd), ["ks"/"vs": (NB, BS, KVH)]};
    pt: (B, MB) int32 page table; pos: (B,) current lengths; ``attend``
    from :func:`_paged_decode_attend`.  The new token's K/V scatter into
    each slot's current (block, offset); released slots (page_table row
    -1) scatter out-of-bounds and are dropped, so a dead slot can never
    corrupt blocks reassigned to other sequences."""
    hd = cfg.hd()
    int8 = _kv_int8(cfg)
    with jax.named_scope("qkv"):
        h = L.apply_norm(x, p["norm1"], cfg.norm_type, cfg.eps)
        q, k, v = _decode_qkv(p["attn"], h, cfg)
        if rope_cs is not None:
            cos, sin = rope_cs
            q = L.apply_rope(q, cos[:, None], sin[:, None])
            k = L.apply_rope(k, cos[:, None], sin[:, None])

    with jax.named_scope("kv_write"):
        nb, bs = lcache["k"].shape[0], lcache["k"].shape[1]
        mb = pt.shape[1]
        blk_idx = jnp.clip(pos // bs, 0, mb - 1)              # (B,)
        blk_off = pos % bs
        blk_id = jnp.take_along_axis(pt, blk_idx[:, None], axis=1)[:, 0]
        safe = jnp.where(blk_id < 0, nb, blk_id)          # nb = OOB -> drop

        lcache = dict(lcache)
        if int8:
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            lcache["k"] = lcache["k"].at[safe, blk_off].set(kq, mode="drop")
            lcache["v"] = lcache["v"].at[safe, blk_off].set(vq, mode="drop")
            lcache["ks"] = lcache["ks"].at[safe, blk_off].set(ks,
                                                              mode="drop")
            lcache["vs"] = lcache["vs"].at[safe, blk_off].set(vs,
                                                              mode="drop")
        else:
            lcache["k"] = lcache["k"].at[safe, blk_off].set(
                k.astype(lcache["k"].dtype), mode="drop")
            lcache["v"] = lcache["v"].at[safe, blk_off].set(
                v.astype(lcache["v"].dtype), mode="drop")

        if cpool is not None:
            lcache = cpool(lcache, 2)
    out = attend(q * (hd ** -0.5), lcache, pt, pos + 1)
    with jax.named_scope("out_proj"):
        if crep is not None:
            # heads mix in the wo contraction: gather them whole first so
            # the reduction keeps single-device summation order (bitwise
            # contract)
            out = crep(out)
        x = x + _decode_out_proj(p["attn"], out, x.dtype)
    with jax.named_scope("mlp"):
        x = x + _mlp_or_moe(p, x[:, None, :], cfg, decode=True)[:, 0]
    return x, lcache


def _decode_step_paged(params: Params, cfg: ModelConfig, cache: Cache,
                       tokens: jax.Array, positions, mesh=None,
                       mode: str = "oracle") -> Tuple[jax.Array, Cache]:
    b = tokens.shape[0]
    crep, cpool = _serve_mesh_helpers(cfg, mesh)
    attend = _paged_decode_attend(cfg, mode, mesh)
    if mesh is not None:
        params = crep(params)
        tokens = crep(tokens)
        cache = dict(cache)
        cache["lens"] = crep(cache["lens"])
        cache["page_table"] = crep(cache["page_table"])
        cache["attn"] = cpool(cache["attn"], 3)
    pos = cache["lens"] if positions is None else positions
    if mesh is not None and positions is not None:
        pos = crep(pos)
    with jax.named_scope("embed"):
        x = L.embed_lookup(params["embed"], tokens).astype(_cdt(cfg))
    with jax.named_scope("qkv"):
        rp = pos if cfg.rope_type != "mrope" else \
            jnp.broadcast_to(pos, (3, b))
        rope_cs = _rope_cos_sin(cfg, rp)
    pt = cache["page_table"]
    lcrep = crep if mesh is not None else None
    lcpool = cpool if mesh is not None else None

    def body(h, inp):
        lp, lc = inp
        return _attn_decode_layer_paged(lp, h, cfg, lc, pt, pos, rope_cs,
                                        attend, crep=lcrep, cpool=lcpool)

    x, new_attn = lax.scan(body, x, (params["blocks"], cache["attn"]))
    with jax.named_scope("lm_head"):
        x = L.apply_norm(x, params["final_norm"], cfg.norm_type, cfg.eps)
        logits = L.lm_head(_head_weight(params, cfg), x)
    if mesh is not None:
        logits = crep(logits)
        new_attn = cpool(new_attn, 3)
    new_cache = dict(cache)
    new_cache["attn"] = new_attn
    # a slot with no first block is released/empty: pin its length at 0 so
    # it never re-grows an attention window over garbage between reuses
    live = pt[:, 0] >= 0
    new_cache["lens"] = jnp.where(live, pos + 1, 0)
    return logits, new_cache


def _ssm_decode_layer(p, x, cfg: ModelConfig, conv_state, ssm_state):
    h = L.apply_norm(x, p["norm1"], cfg.norm_type, cfg.eps)
    y, (cs, ss) = S.mamba2_decode_step(p["ssm"], h, _ssm_dims(cfg),
                                       conv_state, ssm_state)
    return x + y, (cs, ss)


def decode_step(params: Params, cfg: ModelConfig, cache: Cache,
                tokens: jax.Array, positions: Optional[jax.Array] = None,
                mesh=None) -> Tuple[jax.Array, Cache]:
    """tokens (B,) int32 -> (logits (B, V) f32, updated cache).

    A cache carrying a ``page_table`` (init_paged_cache) routes through the
    paged decode path, whose attention reads the pool as
    :func:`prefill_fused_mode` selects (the Pallas paged decode kernel on
    a TPU, the jnp gather elsewhere); the dense per-slot reservation is
    the default.  ``mesh`` (paged path only) applies the storage-sharded
    / compute-replicated serving constraints — see
    :func:`_serve_mesh_helpers`; dense decode ignores it."""
    if "page_table" in cache:
        return _decode_step_paged(params, cfg, cache, tokens, positions,
                                  mesh=mesh, mode=prefill_fused_mode())
    b = tokens.shape[0]
    pos = cache["lens"] if positions is None else positions  # (B,) int32
    x = L.embed_lookup(params["embed"], tokens).astype(_cdt(cfg))

    rp = pos if cfg.rope_type != "mrope" else \
        jnp.broadcast_to(pos, (3, b))
    rope_cs = _rope_cos_sin(cfg, rp)

    fam = cfg.family
    new_cache = dict(cache)
    if fam == "moe" and cfg.moe_every > 1:
        def one(h, inp):
            lp, lc = inp
            return _attn_decode_layer(lp, h, cfg, lc, pos, rope_cs)

        def pat_body(h, inp):
            (lp_dense, lp_moe), (lc_dense, lc_moe) = inp
            h, lc_dense2 = lax.scan(one, h, (lp_dense, lc_dense))
            h, lc_moe2 = one(h, (lp_moe, lc_moe))
            return h, (lc_dense2, lc_moe2)

        x, (nd, nm) = lax.scan(
            pat_body, x,
            ((params["blocks_dense"], params["blocks_moe"]),
             (cache["attn_dense"], cache["attn_moe"])))
        new_cache["attn_dense"] = nd
        new_cache["attn_moe"] = nm

    elif fam in ("dense", "vlm", "moe"):
        def body(h, inp):
            lp, lc = inp
            h2, lc2 = _attn_decode_layer(lp, h, cfg, lc, pos, rope_cs)
            return h2, lc2
        x, new_attn = lax.scan(body, x, (params["blocks"], cache["attn"]))
        new_cache["attn"] = new_attn

    elif fam == "ssm":
        def body(h, inp):
            lp, (cs, ss) = inp
            h2, (cs2, ss2) = _ssm_decode_layer(lp, h, cfg, cs, ss)
            return h2, (cs2, ss2)
        x, (ncs, nss) = lax.scan(
            body, x, (params["blocks"],
                      (cache["ssm"]["conv"], cache["ssm"]["state"])))
        new_cache["ssm"] = {"conv": ncs, "state": nss}

    elif fam == "hybrid":
        shared = params["shared_attn"]

        def inner(h, inp):
            lp, (cs, ss) = inp
            h2, st = _ssm_decode_layer(lp, h, cfg, cs, ss)
            return h2, st

        def super_body(h, inp):
            lp_super, (ssm_c, attn_c) = inp
            h, ssm_c2 = lax.scan(inner, h, (lp_super, ssm_c))
            h, attn_c2 = _attn_decode_layer(shared, h, cfg, attn_c, pos,
                                            rope_cs)
            return h, (ssm_c2, attn_c2)

        main_sts = (cache["ssm_main"]["conv"], cache["ssm_main"]["state"])
        x, (nmain, nattn) = lax.scan(
            super_body, x,
            (params["blocks_main"], (main_sts, cache["attn"])))
        x, ntail = lax.scan(
            inner, x, (params["blocks_tail"],
                       (cache["ssm_tail"]["conv"], cache["ssm_tail"]["state"])))
        new_cache["ssm_main"] = {"conv": nmain[0], "state": nmain[1]}
        new_cache["ssm_tail"] = {"conv": ntail[0], "state": ntail[1]}
        new_cache["attn"] = nattn
    else:
        raise ValueError(fam)

    x = L.apply_norm(x, params["final_norm"], cfg.norm_type, cfg.eps)
    logits = L.lm_head(_head_weight(params, cfg), x)
    new_cache["lens"] = pos + 1
    return logits, new_cache


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            max_seq: Optional[int] = None) -> Tuple[jax.Array, Cache]:
    """Process a full prompt, build the cache, return last-token logits."""
    if "embeds" in batch:
        b, s = batch["embeds"].shape[:2]
    else:
        b, s = batch["tokens"].shape
    max_seq = max_seq or s
    positions = _default_positions(cfg, b, s, batch)
    x = embed_inputs(params, cfg, batch)
    hidden, parts = forward_hidden(params, cfg, x, positions,
                                   collect_cache=True)

    cache = init_cache(cfg, b, max_seq)
    cache["lens"] = jnp.full((b,), s, jnp.int32)
    int8 = _kv_int8(cfg)

    def fill_attn(dst, kv):
        # k/v buffers: (…lead, S, KVH, hd); scales: (…lead, S, KVH).
        k, v = kv
        dst = dict(dst)
        if int8:
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            dst["k"] = dst["k"].at[..., :s, :, :].set(kq)
            dst["v"] = dst["v"].at[..., :s, :, :].set(vq)
            dst["ks"] = dst["ks"].at[..., :s, :].set(ks)
            dst["vs"] = dst["vs"].at[..., :s, :].set(vs)
            return dst
        dst["k"] = dst["k"].at[..., :s, :, :].set(k.astype(dst["k"].dtype))
        dst["v"] = dst["v"].at[..., :s, :, :].set(v.astype(dst["v"].dtype))
        return dst

    fam = cfg.family
    if fam == "moe" and cfg.moe_every > 1:
        kvd, kvm = parts           # (n_pat, me-1, B,S,…) and (n_pat, B,S,…)
        cache["attn_dense"] = fill_attn(cache["attn_dense"], kvd)
        cache["attn_moe"] = fill_attn(cache["attn_moe"], kvm)
    elif fam in ("dense", "vlm", "moe"):
        cache["attn"] = fill_attn(cache["attn"], parts)
    elif fam == "ssm":
        conv, st = parts
        cache["ssm"] = {"conv": conv, "state": st}
    elif fam == "hybrid":
        main_sts, attn_kvs, tail_sts = parts
        cache["ssm_main"] = {"conv": main_sts[0], "state": main_sts[1]}
        cache["ssm_tail"] = {"conv": tail_sts[0], "state": tail_sts[1]}
        cache["attn"] = fill_attn(cache["attn"], attn_kvs)

    logits = L.lm_head(_head_weight(params, cfg), hidden[:, -1])
    return logits, cache


def prefill_chunk(params: Params, cfg: ModelConfig, tokens_chunk: jax.Array,
                  cache: Cache, slot: int, pos_offset: int
                  ) -> Tuple[jax.Array, Cache]:
    """Prefill one prompt chunk of slot ``slot`` directly into a paged
    cache (Sarathi/vLLM-style chunked prefill).

    ``tokens_chunk`` holds ``c`` tokens at global positions
    ``pos_offset .. pos_offset + c - 1``.  The chunk's K/V rows are
    written into the slot's pool blocks at their (block, offset)
    coordinates, and its queries attend the ``pos_offset`` prefix rows
    already in the pool (read back through the page table, dequantized
    for int8 pools) plus the chunk itself, causally — ``pos_offset`` is
    *data*, threaded into rope, the causal mask and the prefix-validity
    mask (layers.attention_chunk_merge is the jnp oracle;
    kernels/flash_prefill.py carries the same per-row offsets via
    scalar prefetch on TPU).  Numerics contract, verified by
    tests/test_scheduler.py:

      * a single chunk covering the whole prompt is **bit-identical** to
        the one-shot :func:`prefill` (an empty prefix segment merges
        with weight exactly zero);
      * composed over multiple chunks, every query still reduces over
        exactly the prefix-plus-own-chunk key set — the prefix and chunk
        segments are reduced separately and merged by softmax
        renormalization, so float pools match one-shot KV rows and
        logits to last-ulp reassociation tolerance with identical
        greedy streams;
      * for int8 pools the stored codes match within the +-1 code that
        last-ulp projection differences can tip across a rounding
        boundary; cross-chunk attention additionally reads the
        requantized prefix — the same approximation the decode path
        already lives with.

    The caller must have grown the slot's block list to cover
    ``pos_offset + c`` tokens and republished ``cache["page_table"]``
    before calling (the serving scheduler does both).  Returns the
    chunk's last-position logits ``(1, V)`` and the updated cache with
    ``lens[slot] = pos_offset + c``.

    This is the single-sequence view of :func:`prefill_chunk_batch`
    (B = 1); see there for the jit/donation story.
    """
    toks = jnp.asarray(tokens_chunk, jnp.int32).reshape(1, -1)
    return prefill_chunk_batch(params, cfg, toks, cache, [slot], pos_offset)


def prefill_chunk_batch(params: Params, cfg: ModelConfig,
                        tokens_chunks: jax.Array, cache: Cache,
                        slots, pos_offsets,
                        page_table=None,
                        chunk_lens=None, mesh=None
                        ) -> Tuple[jax.Array, Cache]:
    """Prefill one prompt chunk for up to B sequences in ONE device call —
    **shape-stable**: rows may carry *different* chunk lengths and
    position offsets, so the engine batches every chunk of a step (and
    pads to a fixed ``(max_slots, prefill_chunk_tokens)`` extent) instead
    of grouping by shape.

    ``tokens_chunks`` is ``(B, c)``; ``slots`` lists B slot ids, distinct
    where valid — a negative slot marks a padding row that computes
    nothing visible (its KV writes and ``lens`` update are dropped, its
    logits row is garbage).  ``pos_offsets`` is an int or per-row (B,)
    array of each row's global start position; ``chunk_lens`` (None = all
    rows full) gives each row's valid token count — rows are masked past
    it.  Returns per-row last-valid-position logits ``(B, V)`` and the
    updated cache with ``lens[slot] = pos_offset + chunk_len`` per valid
    row.

    Everything data-like is *traced*: offsets, lengths, slot ids, block
    coordinates and each row's full page-table row (the prefix is read as
    a masked gather over the whole row rather than a ``pos_offset``-sized
    slice).  The jit compile key is therefore just the padded ``(B, c)``
    extent plus the pool shapes — **one compile per pool key**, however
    traffic mixes chunk lengths, offsets, or batch composition
    (tests/test_compile_stability.py asserts the bound; the engine
    reports it via :func:`prefill_chunk_compiles`).

    Numerics: masked keys carry exactly-zero probability mass and padded
    rows/positions never write, so a padded call is bit-identical to the
    equivalent unpadded per-shape calls — and the whole-prompt single
    chunk stays bit-identical to one-shot :func:`prefill` (f32; int8
    pools additionally match code-for-code).  For the MoE family,
    capacity-limited routing is batch-dependent (it already was under
    shape-grouped batching) — the exactness contract is stated for the
    families whose per-token compute is row-independent.

    The traced body is jitted with the cache **donated** so each call
    updates the pool in place instead of copying it.  ``page_table`` may
    carry the caller's host-side copy of ``cache["page_table"]`` (the
    engine publishes both from the same allocator state) to spare a
    device readback per call.
    """
    args = _chunk_call_args(tokens_chunks, cache, slots, pos_offsets,
                            page_table, chunk_lens)
    return _prefill_chunk_fn(cfg, prefill_fused_mode(), mesh=mesh)(
        params, cache, *args)


def verify_chunk_batch(params: Params, cfg: ModelConfig,
                       tokens_chunks: jax.Array, cache: Cache,
                       slots, pos_offsets,
                       page_table=None,
                       chunk_lens=None, mesh=None
                       ) -> Tuple[jax.Array, Cache]:
    """Multi-token speculative *verify* step: exactly
    :func:`prefill_chunk_batch` — same traced addressing, same fused /
    oracle prefix read, same KV scatter — but returning logits for **all**
    ``c`` chunk positions, ``(B, c, V)``, instead of only each row's last.

    The draft tokens of each row are fed as a k-token "chunk" at
    ``pos_offset = kv_len``; position ``j``'s logits row conditions on
    the prefix plus draft tokens ``< j``, which is what the acceptance
    rule samples from.  Rows past ``chunk_lens`` are masked as in
    prefill; their logits are garbage and must not be read.

    Kept as a *separate* jit entry (see :func:`_prefill_chunk_fn`'s
    ``all_logits`` flag) so the wide prefill extent never materializes a
    ``(B, prefill_chunk_tokens, V)`` logits tensor: the engine pads
    verify calls to the narrow ``(max_slots, spec_tokens + 1)`` extent
    and this entry holds its own one-executable-per-pool-key bound,
    probed by :func:`verify_chunk_compiles`.
    """
    args = _chunk_call_args(tokens_chunks, cache, slots, pos_offsets,
                            page_table, chunk_lens)
    return _prefill_chunk_fn(cfg, prefill_fused_mode(), True, mesh=mesh)(
        params, cache, *args)


def _chunk_call_args(tokens_chunks, cache: Cache, slots, pos_offsets,
                     page_table, chunk_lens):
    """Host-side (concrete) addressing shared by the prefill and verify
    chunk entries: each row's chunk lives at fixed (block, offset)
    coordinates in its own leased blocks; positions past the row's valid
    length scatter out of bounds (dropped), so padding can never write
    into a block another sequence leases."""
    if "page_table" not in cache:
        raise ValueError("prefill_chunk requires a paged cache "
                         "(init_paged_cache)")
    toks = jnp.asarray(tokens_chunks, jnp.int32)
    b, c = toks.shape
    slots = np.asarray(slots, np.int32).reshape(-1)
    offs = np.broadcast_to(np.asarray(pos_offsets, np.int32), (b,))
    lens = (np.full((b,), c, np.int32) if chunk_lens is None
            else np.asarray(chunk_lens, np.int32).reshape(-1))
    valid = slots >= 0
    live = slots[valid]
    if len(set(live.tolist())) != len(live):
        raise ValueError(f"slots {slots} must be distinct where valid")
    nb, bs = cache["attn"]["k"].shape[1], cache["attn"]["k"].shape[2]
    max_slots = cache["lens"].shape[0]

    pt = np.asarray(cache["page_table"] if page_table is None
                    else page_table)
    mb = pt.shape[1]
    # vectorized over rows — this runs on the host critical path every
    # step and used to be a Python loop scaling with max_slots
    live_row = valid & (lens > 0)                       # rows that write
    rows = pt[np.where(live_row, slots, 0)]             # (b, mb)
    gpos = offs[:, None] + np.arange(c, dtype=np.int32)[None]     # (b, c)
    in_len = np.arange(c, dtype=np.int32)[None] < lens[:, None]   # (b, c)
    row_blk = np.take_along_axis(rows, np.minimum(gpos // bs, mb - 1),
                                 axis=1)                # (b, c)
    mask = in_len & live_row[:, None]
    bad = ((row_blk < 0) | (gpos >= mb * bs)) & mask
    if bad.any():
        i = int(np.argmax(bad.any(axis=1)))
        raise ValueError(f"slot {slots[i]} page table does not cover "
                         f"rows [{offs[i]}, {offs[i] + lens[i]}) — "
                         "allocate blocks before prefill_chunk")
    chunk_blk = np.where(mask, row_blk, nb).astype(np.int32)
    chunk_off = np.where(mask, gpos % bs, 0).astype(np.int32)
    pt_rows = np.where(live_row[:, None],               # -1 -> 0; masked
                       np.maximum(rows, 0), 0).astype(np.int32)
    safe_slots = np.where(valid, slots, max_slots)     # OOB -> lens drop

    return (toks,
            jnp.asarray(chunk_blk),
            jnp.asarray(chunk_off),
            jnp.asarray(pt_rows),
            jnp.asarray(safe_slots),
            jnp.asarray(offs),
            jnp.asarray(np.where(valid, lens, 0)))


def prefill_fused_mode() -> str:
    """Which path both paged-attention reads take: chunked prefill's
    prefix read and the decode step's attention.

    ``"kernel"`` runs the Pallas kernels
    (`kernels.paged_prefill_attention`, `kernels.paged_decode_attention`):
    K/V are read through the page table inside each kernel's index_map —
    live pages only, no materialized gather.  ``"oracle"`` keeps the jnp
    gathers (`layers.attention_chunk_merge`,
    `layers.paged_attention_decode`).  ``"interpret"`` is the kernels in
    Pallas interpret mode (CPU-executable — what the parity tests and the
    bench's bit-identity probe run).

    Policy: the ``REPRO_FUSED_PREFILL`` env var (kernel/oracle/interpret,
    with on/1 and off/0 aliases) wins; default is the kernels on real TPU
    backends and the oracle elsewhere, so CPU test/bench numerics are
    unchanged by default.
    """
    v = os.environ.get("REPRO_FUSED_PREFILL", "").strip().lower()
    if v in ("kernel", "on", "1", "true"):
        return "kernel"
    if v in ("oracle", "off", "0", "false"):
        return "oracle"
    if v == "interpret":
        return "interpret"
    return "kernel" if jax.default_backend() == "tpu" else "oracle"


def prefill_chunk_compiles(cfg: ModelConfig, mesh=None) -> int:
    """How many distinct XLA executables back the chunked-prefill step
    for ``cfg`` so far in this process — the shape-stability probe.

    Counts the jit-cache entries of the traced chunk body (one per
    distinct padded extent + pool shape, i.e. per *pool key*).  The
    engine snapshots it into ``metrics["prefill_compiles"]`` /
    ``plan_log``; tests and the shape-churn benchmark assert it stays at
    one per pool key while traffic churns chunk lengths and offsets.

    ``mesh`` selects that mesh's own jitted entry — the contract under
    tensor parallelism is one executable per (pool key, mesh shape), and
    because each mesh owns a separate jit cache, churning one mesh can
    never recompile another's executables."""
    return _prefill_chunk_fn(cfg, prefill_fused_mode(),
                             mesh=mesh)._cache_size()


def verify_chunk_compiles(cfg: ModelConfig, mesh=None) -> int:
    """Same probe as :func:`prefill_chunk_compiles` for the verify entry
    (the ``all_logits=True`` twin of the chunk step).  The engine pads
    every verify call to one fixed ``(max_slots, spec_tokens + 1)``
    extent, so this too must stay at one executable per pool key."""
    return _prefill_chunk_fn(cfg, prefill_fused_mode(), True,
                             mesh=mesh)._cache_size()


@functools.lru_cache(maxsize=None)
def _prefill_chunk_fn(cfg: ModelConfig, mode: str = "oracle",
                      all_logits: bool = False, mesh=None):
    """Build (once per config + prefix-path mode) the jitted,
    cache-donating chunk step.

    All extents inside are data: ``offs``/``lens`` drive rope, the
    causal mask, key validity, the KV scatter and the ``lens`` update,
    so the compile key is only the padded shapes.  The prefix is read
    either by gathering each row's whole page-table row and masking keys
    at positions ``>= offs[row]`` (mode "oracle"), or through the fused
    `kernels.paged_prefill_attention` Pallas kernel whose index_map
    dereferences the page table under scalar prefetch and skips dead
    tiles (mode "kernel"/"interpret") — see :func:`prefill_fused_mode`.
    Either way the per-row offsets/lengths stay traced, so the
    one-compile-per-pool-key bound holds for both paths.

    ``all_logits=True`` builds the speculative-verify twin: identical
    body, but the head projects every chunk position — ``(B, c, V)`` —
    instead of gathering each row's last valid position first.  It lives
    under its own lru/jit entry so verify's narrow padded extent never
    shares (or churns) the prefill executable.

    ``mesh`` applies the storage-sharded / compute-replicated serving
    constraints (:func:`_serve_mesh_helpers`) and — being part of the
    lru key — gives every mesh its own jitted entry, so the compile
    contract is one executable per (pool key, mesh shape) and meshes
    never invalidate each other."""
    hd = cfg.hd()
    kvh = cfg.n_kv_heads
    int8 = _kv_int8(cfg)
    fused = mode != "oracle"
    if fused:
        from repro.kernels import ops as KO
    acfg = L.AttnConfig(cfg.n_heads, kvh, hd, causal=True,
                        q_chunk=cfg.q_chunk)
    crep, cpool = _serve_mesh_helpers(cfg, mesh)

    # named chunk_step, so that its program is ``jit_chunk_step``
    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk_step(params, cache, toks, chunk_blk, chunk_off, pt_rows,
                   slots, offs, lens):
        if mesh is not None:
            params = crep(params)
            toks, chunk_blk, chunk_off, pt_rows, slots, offs, lens = crep(
                (toks, chunk_blk, chunk_off, pt_rows, slots, offs, lens))
            cache = dict(cache)
            cache["lens"] = crep(cache["lens"])
            cache["page_table"] = crep(cache["page_table"])
            cache["attn"] = cpool(cache["attn"], 3)
        b, c = toks.shape
        bs = cache["attn"]["k"].shape[2]
        mb = pt_rows.shape[1]

        q_pos = offs[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        positions = q_pos
        if cfg.rope_type == "mrope":
            positions = jnp.broadcast_to(positions, (3, b, c))
        with jax.named_scope("qkv"):
            rope_cs = _rope_cos_sin(cfg, positions)
        with jax.named_scope("embed"):
            x = embed_inputs(params, cfg, {"tokens": toks})

        # key validity: pool row t sits at global position t (page
        # tables are position-ordered) and is live strictly below the
        # row's offset (the chunk's own keys are attended in float,
        # pre-quantization); a chunk key is live below the row's valid
        # length.
        pfx_valid = jnp.arange(mb * bs, dtype=jnp.int32)[None] < offs[:, None]
        chunk_valid = jnp.arange(c, dtype=jnp.int32)[None] < lens[:, None]

        def body(h, inp):
            lp, lc = inp
            with jax.named_scope("qkv"):
                hn = L.apply_norm(h, lp["norm1"], cfg.norm_type, cfg.eps)
                q = qeinsum("bsd,hkd->bshk", hn, lp["attn"]["wq"])
                k = qeinsum("bsd,hkd->bshk", hn, lp["attn"]["wk"])
                v = qeinsum("bsd,hkd->bshk", hn, lp["attn"]["wv"])
                if rope_cs is not None:
                    cos, sin = rope_cs
                    q = L.apply_rope(q, cos[:, :, None], sin[:, :, None])
                    k = L.apply_rope(k, cos[:, :, None], sin[:, :, None])
            if fused:
                # prefix read through the page table inside the kernel's
                # index_map: O(offs) live tiles fetched, dead tiles
                # skipped, int8 dequantized in-kernel.  Under a mesh the
                # pool is gathered whole first — the Pallas kernel
                # addresses the full KVH extent, not a shard — and, as
                # the partitioner cannot split a Mosaic call, every
                # device runs it on its replicated operands.
                with jax.named_scope("attend"):
                    lck = crep(lc) if mesh is not None else lc
                    prefix = functools.partial(
                        KO.paged_prefill_attention,
                        interpret=(mode == "interpret"))
                    if mesh is not None:
                        prefix = jax.shard_map(
                            prefix, mesh=mesh, in_specs=PartitionSpec(),
                            out_specs=PartitionSpec(), check_vma=False)
                    pfx_state = prefix(
                        q * (hd ** -0.5), lck["k"], lck["v"], pt_rows, offs,
                        lens, lck["ks"] if int8 else None,
                        lck["vs"] if int8 else None)
                    out = L.attention_chunk_merge(q * (hd ** -0.5), None,
                                                  None, k, v, acfg, q_pos,
                                                  None, chunk_valid,
                                                  pfx_state=pfx_state)
            else:
                # each row gathers ITS page-table row (shared blocks may
                # appear in several rows — reads never conflict); dead or
                # not-yet-written positions are masked via k_valid
                with jax.named_scope("kv_gather"):
                    kp = lc["k"][pt_rows].reshape(b, mb * bs, kvh, hd)
                    vp = lc["v"][pt_rows].reshape(b, mb * bs, kvh, hd)
                    if int8:
                        kp = kp.astype(jnp.float32) * lc["ks"][
                            pt_rows].reshape(b, mb * bs, kvh)[..., None]
                        vp = vp.astype(jnp.float32) * lc["vs"][
                            pt_rows].reshape(b, mb * bs, kvh)[..., None]
                with jax.named_scope("attend"):
                    out = L.attention_chunk_merge(q * (hd ** -0.5),
                                                  kp.astype(k.dtype),
                                                  vp.astype(v.dtype), k, v,
                                                  acfg, q_pos, pfx_valid,
                                                  chunk_valid)
            with jax.named_scope("out_proj"):
                if mesh is not None:
                    # heads mix here: gather them whole so the wo
                    # reduction keeps single-device summation order
                    # (bitwise contract)
                    out = crep(out)
                out = qeinsum("bshk,dhk->bsd", out, lp["attn"]["wo"])
                h = h + out.astype(h.dtype)
            with jax.named_scope("mlp"):
                h = h + _mlp_or_moe(lp, h, cfg)

            with jax.named_scope("kv_write"):
                lc = dict(lc)
                if int8:
                    kq_, ks_ = _quantize_kv(k)
                    vq_, vs_ = _quantize_kv(v)
                    lc["k"] = lc["k"].at[chunk_blk, chunk_off].set(
                        kq_, mode="drop")
                    lc["v"] = lc["v"].at[chunk_blk, chunk_off].set(
                        vq_, mode="drop")
                    lc["ks"] = lc["ks"].at[chunk_blk, chunk_off].set(
                        ks_, mode="drop")
                    lc["vs"] = lc["vs"].at[chunk_blk, chunk_off].set(
                        vs_, mode="drop")
                else:
                    lc["k"] = lc["k"].at[chunk_blk, chunk_off].set(
                        k.astype(lc["k"].dtype), mode="drop")
                    lc["v"] = lc["v"].at[chunk_blk, chunk_off].set(
                        v.astype(lc["v"].dtype), mode="drop")
                if mesh is not None:
                    lc = cpool(lc, 2)
            return h, lc

        x, new_attn = lax.scan(body, x, (params["blocks"], cache["attn"]))
        with jax.named_scope("lm_head"):
            x = L.apply_norm(x, params["final_norm"], cfg.norm_type,
                             cfg.eps)
            if all_logits:
                logits = L.lm_head(_head_weight(params, cfg), x)  # (b,c,V)
            else:
                last = jnp.clip(lens - 1, 0, c - 1)
                logits = L.lm_head(_head_weight(params, cfg),
                                   x[jnp.arange(b), last])
        new_cache = dict(cache)
        new_cache["attn"] = cpool(new_attn, 3) if mesh is not None \
            else new_attn
        new_cache["lens"] = cache["lens"].at[slots].set(offs + lens,
                                                       mode="drop")
        if mesh is not None:
            logits = crep(logits)
            new_cache["lens"] = crep(new_cache["lens"])
        return logits, new_cache

    return chunk_step
