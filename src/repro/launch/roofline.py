"""Roofline assembly: three terms per (arch x shape x mesh) cell.

Methodology (documented in EXPERIMENTS.md §Roofline):

  compute term    exact algorithmic FLOPs from the jaxpr walker
                  (launch/flops.py — scan-length aware; remat recompute
                  included), divided by chips × peak.
  memory term     analytic per-device HBM traffic model (below).
                  ``cost_analysis()['bytes accessed']`` counts while
                  bodies once, so it can only serve as a cross-check.
  collective term per-device ICI wire bytes from the optimized HLO with
                  while-trip correction (launch/hlo_cost.py), divided by
                  link bandwidth.

Analytic HBM traffic (per device, per step):

  train    opt update reads p,m,v and writes p,m,v (6·P·4B) + fwd reads
           P once per microbatch + bwd reads P (transposes) + remat
           re-reads P + grad write/read (2·P·4B)
           + activations: ~6 passes over the per-layer residual stream
           (write fwd, read/write remat, read bwd) × L layers.
  prefill  weight bytes (int8 + scales) + KV-cache write + ~4 activation
           passes per layer.
  decode   weight bytes + KV-cache read (+ write of 1 token) + O(B·D)
           activations — the paper's regime: weight/cache streaming IS
           the roofline.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np

from repro.configs.base import ModelConfig, ShapeCell
from repro.core.quantization import QuantizedTensor

PEAK_FLOPS_BF16 = 197e12          # per chip
PEAK_FLOPS_INT8 = 394e12
HBM_BW = 819e9                    # B/s per chip
ICI_BW = 50e9                     # B/s per link
V5E_POWER_W = 170.0               # per-chip board power under load

# Published per-chip peaks keyed by ``jax.Device.device_kind`` (Google
# Cloud documentation, "TPU v5e").  A modelled figure is printed only for
# a device kind listed here.
PEAKS_BY_DEVICE_KIND = {
    "TPU v5 lite": {"flops_bf16": PEAK_FLOPS_BF16,
                    "flops_int8": PEAK_FLOPS_INT8, "hbm_bw": HBM_BW},
}


def step_joules(bytes_moved: float, flops: float,
                power_w: float = V5E_POWER_W,
                hbm_bw: float = HBM_BW,
                peak_flops: float = PEAK_FLOPS_BF16) -> float:
    """Roofline energy for one device call: the call takes
    max(memory time, compute time) and the chip burns ``power_w`` for
    that long.  This is the serving-stack energy model — the engine
    feeds it per-step bytes (weights + live KV tiles + activations) and
    FLOPs, and the benchmark divides tokens by the accumulated joules
    (the paper's tokens/J metric, here from the analytic roofline
    rather than a power meter)."""
    t = max(bytes_moved / hbm_bw, flops / peak_flops)
    return t * power_w


def tree_bytes(tree) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, QuantizedTensor)):
        if isinstance(leaf, QuantizedTensor):
            total += int(np.prod(leaf.q.shape)) * leaf.q.dtype.itemsize
            total += int(np.prod(leaf.scale.shape)) * 4
        else:
            total += int(np.prod(leaf.shape)) * jax.dtypes.canonicalize_dtype(
                leaf.dtype).itemsize
    return total


def per_device_bytes(struct, specs, mesh) -> float:
    """Per-device bytes of a pytree given its PartitionSpecs — divides each
    leaf by the product of its sharded axis sizes (exact for ep_data-style
    2-D-sharded experts, where the old /16 assumption was 16x off)."""
    from jax.sharding import PartitionSpec as P

    flat_specs, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    flat_struct = treedef.flatten_up_to(struct)
    total = 0.0
    for spec, leaf in zip(flat_specs, flat_struct):
        nbytes = int(np.prod(leaf.shape)) * jax.dtypes.canonicalize_dtype(
            leaf.dtype).itemsize
        shards = 1
        if isinstance(spec, P):
            for axis in spec:
                for a in (axis if isinstance(axis, tuple) else (axis,)):
                    if a is not None:
                        shards *= mesh.shape[a]
        total += nbytes / shards
    return total


def analytic_bytes(cfg: ModelConfig, cell: ShapeCell, n_dev: int,
                   param_bytes_global: int, cache_bytes_global: int = 0,
                   microbatches: int = 1,
                   param_bytes_per_dev: float = 0.0) -> Dict[str, float]:
    """Per-device HBM traffic estimate (see module docstring)."""
    model_shards = 16                      # model axis of both meshes
    p_dev = param_bytes_per_dev or \
        param_bytes_global / model_shards  # params replicated over data
    b_loc = max(cell.global_batch // (n_dev // model_shards), 1)
    act_elem = 2                           # bf16 residual stream

    if cell.kind == "train":
        opt_traffic = 6 * (param_bytes_global / model_shards / 4)  # rough: m,v f32 ZeRO over data
        # params are f32 in train; read fwd (per microbatch), read bwd,
        # remat re-read, grad write+read
        w_traffic = (2 * microbatches + 3) * p_dev
        layers = max(cfg.n_layers, 1)
        act = 6 * layers * b_loc * cell.seq_len * cfg.d_model * act_elem
        total = w_traffic + opt_traffic + act
        return {"weights": w_traffic, "opt": opt_traffic, "acts": act,
                "total": total}

    if cell.kind == "prefill":
        layers = max(cfg.n_layers, 1)
        act = 4 * layers * b_loc * cell.seq_len * cfg.d_model * act_elem
        cache_w = cache_bytes_global / n_dev
        total = p_dev + act + cache_w
        return {"weights": p_dev, "acts": act, "cache": cache_w,
                "total": total}

    # decode: the paper's regime
    cache_r = cache_bytes_global / n_dev
    act = 8 * cfg.n_layers * b_loc * cfg.d_model * 4
    total = p_dev + cache_r + act
    return {"weights": p_dev, "cache": cache_r, "acts": act, "total": total}


def assemble(cfg: ModelConfig, cell: ShapeCell, n_dev: int,
             algo_flops_global: float, model_flops_global: float,
             mem: Dict[str, float], coll_bytes_dev: float,
             raw_cost: Dict[str, float]) -> Dict[str, Any]:
    flops_dev = algo_flops_global / n_dev
    t_compute = flops_dev / PEAK_FLOPS_BF16
    t_memory = mem["total"] / HBM_BW
    t_coll = coll_bytes_dev / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    step_time = max(terms.values())
    # roofline fraction: useful model flops at peak vs. the achievable step
    # (meaningful for compute-bound cells); bw_fraction: how much of the
    # step is mandatory HBM streaming (the decode metric — the paper's
    # regime is weight/cache streaming, where t_memory IS the floor).
    ideal = (model_flops_global / n_dev) / PEAK_FLOPS_BF16
    return {
        "arch": cfg.arch_id, "shape": cell.name, "devices": n_dev,
        "bw_fraction": t_memory / step_time if step_time else 0.0,
        "algo_flops_global": algo_flops_global,
        "model_flops_global": model_flops_global,
        "useful_flop_ratio": model_flops_global / algo_flops_global
        if algo_flops_global else 0.0,
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "est_step_time_s": step_time,
        "roofline_fraction": ideal / step_time if step_time else 0.0,
        "mem_breakdown": mem,
        "collective_bytes_dev": coll_bytes_dev,
        "raw_cost_analysis": raw_cost,
    }
