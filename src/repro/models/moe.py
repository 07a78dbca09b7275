"""Expert-parallel Mixture-of-Experts.

This is the paper's recommendation-system partitioning (T1) applied to MoE:
the "sparse side" (experts) is model-parallel across the ``experts`` mesh
axis while dense compute stays data-parallel; tokens move device-to-device
with all_to_all (T9: no host intermediary) and return to their source shard
("results of the sparse lookups gathered to the dense partition").

Dispatch is sort-based (no one-hot einsums): entries are ranked within their
expert in arrival order and dropped beyond a static capacity — the same
first-come-first-served semantics the reference path uses, so the shard_map
path on a (1,1) mesh is bit-identical to ``moe_ref``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import activation_fn, mk_param
from repro.sharding.rules import (Logical, current_ctx, logical_to_spec,
                                  mesh_axis_names, mesh_axis_size)

CAP_MIN = 4   # decode batches route few tokens/expert; keep headroom


def init_moe(cfg: ModelConfig, key):
    dt = jnp.dtype(cfg.param_dtype)
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    E, Ep = m.num_experts, m.padded_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": mk_param(ks[0], (d, E), ("embed", None), jnp.float32),
        "wg": mk_param(ks[1], (Ep, d, f), ("experts", "embed", "expert_mlp"), dt),
        "wu": mk_param(ks[2], (Ep, d, f), ("experts", "embed", "expert_mlp"), dt),
        "wd": mk_param(ks[3], (Ep, f, d), ("experts", "expert_mlp", "embed"), dt),
    }
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        p["shared"] = {
            "w_gate": mk_param(ks[4], (d, fs), ("embed", "mlp"), dt),
            "w_up": mk_param(ks[4], (d, fs), ("embed", "mlp"), dt),
            "w_down": mk_param(ks[4], (fs, d), ("mlp", "embed"), dt),
        }
    return p


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = math.ceil(tokens * m.top_k / m.num_experts * m.capacity_factor)
    return max(c, min(CAP_MIN, tokens * m.top_k))


def _route(x_tok, router_w, cfg: ModelConfig):
    """x_tok (T,d) -> (top-k idx (T,k), weights (T,k) fp32, aux load loss)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x_tok.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # switch-style load-balance aux: E * sum_e f_e * p_e
    T = x_tok.shape[0]
    counts = jnp.zeros((m.num_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f_e = counts / (T * m.top_k)
    p_e = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(f_e * p_e)
    return idx, w, aux


def _dispatch_indices(e_flat, E_local: int, C: int, ES: int):
    """Entry -> slot in the (ES, E_local, C) send buffer; overflow -> OOB."""
    n = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    es_sorted = e_flat[order]
    first = jnp.searchsorted(es_sorted, es_sorted, side="left")
    pos_sorted = jnp.arange(n) - first
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = pos < C
    dest = e_flat // E_local
    slot = dest * (E_local * C) + (e_flat % E_local) * C + pos
    slot = jnp.where(keep, slot, ES * E_local * C)        # OOB -> dropped
    return slot, keep


def _expert_ffn(xin, wg, wu, wd, cfg: ModelConfig, psum_axes):
    """xin (E_local, N, d); expert weights already local slices."""
    act = activation_fn(cfg.activation)
    g = jnp.einsum("end,edf->enf", xin, wg)
    u = jnp.einsum("end,edf->enf", xin, wu)
    h = act(g) * u
    y = jnp.einsum("enf,efd->end", h, wd)
    if psum_axes:
        y = jax.lax.psum(y, psum_axes)
    return y


def _moe_local(x_tok, router_w, wg, wu, wd, cfg: ModelConfig,
               a2a_axes: Tuple[str, ...] = (), psum_axes: Tuple[str, ...] = (),
               es: int = 1):
    """Per-shard MoE body. With es=1 and no axes this is the pure reference."""
    T, d = x_tok.shape
    m = cfg.moe
    E_local = m.padded_experts // es     # dummy experts never receive tokens
    C = _capacity(T, cfg)
    idx, w, aux = _route(x_tok, router_w, cfg)
    e_flat = idx.reshape(-1)                                 # (T*k,)
    t_flat = jnp.repeat(jnp.arange(T), m.top_k)
    slot, keep = _dispatch_indices(e_flat, E_local, C, es)

    buf = jnp.zeros((es * E_local * C, d), x_tok.dtype)
    buf = buf.at[slot].set(x_tok[t_flat], mode="drop")
    buf = buf.reshape(es, E_local * C, d)
    if a2a_axes:
        buf = jax.lax.all_to_all(buf, a2a_axes, split_axis=0, concat_axis=0,
                                 tiled=False)
    # buf[i] now holds source-shard i's tokens for MY experts
    xin = buf.reshape(es, E_local, C, d).transpose(1, 0, 2, 3) \
             .reshape(E_local, es * C, d)
    y = _expert_ffn(xin, wg, wu, wd, cfg, psum_axes)
    y = y.reshape(E_local, es, C, d).transpose(1, 0, 2, 3) \
         .reshape(es, E_local * C, d)
    if a2a_axes:
        y = jax.lax.all_to_all(y, a2a_axes, split_axis=0, concat_axis=0,
                               tiled=False)
    y = y.reshape(es * E_local * C, d)
    vals = jnp.take(y, jnp.minimum(slot, y.shape[0] - 1), axis=0)
    vals = vals * (keep[:, None] & (slot < y.shape[0])[:, None])
    out = jnp.sum(vals.reshape(T, m.top_k, d)
                  * w.astype(vals.dtype)[..., None], axis=1)
    return out.astype(x_tok.dtype), aux


def moe_ref(p, x, cfg: ModelConfig):
    """Pure-jnp single-shard oracle (identical capacity/drop semantics)."""
    B, S, d = x.shape
    y, aux = _moe_local(x.reshape(B * S, d), p["router"], p["wg"], p["wu"],
                        p["wd"], cfg)
    return y.reshape(B, S, d), aux


def moe_apply(p, x, cfg: ModelConfig):
    """Expert-parallel MoE. Uses shard_map when a mesh context is active.

    When the ``experts`` rule spans axes beyond the batch axes (e.g.
    ('data','model') with 512 padded experts over a 256-shard mesh), the
    token/sequence dim is SLICED over those extra axes before dispatch:
    every shard all_to_alls only its own token slice (per-device a2a bytes
    divided by the extra-axis size, no replicated dispatch) and each expert
    holds its full FFN — no expert-TP psum at all."""
    ctx = current_ctx()
    es = mesh_axis_size("experts")
    if cfg.moe.padded_experts % max(es, 1):
        es = 1                      # rejected hint: replicate experts
    if ctx is None or es == 1 and mesh_axis_size("expert_mlp") == 1:
        out, aux = moe_ref(p, x, cfg)
    else:
        mesh = ctx.mesh
        a2a = mesh_axis_names("experts") if es > 1 else ()
        psum = tuple(ax for ax in mesh_axis_names("expert_mlp")
                     if ax not in a2a)
        B, S, d = x.shape
        rules = ctx.rules
        batch_axes = rules.batch if isinstance(rules.batch, (tuple, list)) \
            else (rules.batch,)
        # expert axes not already sharding the batch slice the token dim
        extra = tuple(ax for ax in a2a if ax not in batch_axes)
        extra_n = 1
        for ax in extra:
            extra_n *= mesh.shape.get(ax, 1)
        if extra and S % extra_n:
            extra, extra_n = (), 1          # rejected hint: keep replicated

        def body(x, rw, wg, wu, wd):
            T = x.shape[0] * x.shape[1]
            y, aux = _moe_local(x.reshape(T, d), rw, wg, wu, wd, cfg,
                                a2a_axes=a2a, psum_axes=psum, es=es)
            # aux is per-source-shard; average over the batch shards
            if a2a:
                aux = jax.lax.pmean(aux, a2a)
            return y.reshape(x.shape), aux

        spec = lambda shp, *ax: logical_to_spec(Logical(*ax), ctx.rules, mesh,
                                                tuple(shp))
        x_sp = spec(x.shape, "batch", None, None)
        if extra:
            x_sp = P(x_sp[0] if len(x_sp) else None, extra)
        out, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(x_sp, spec(p["router"].shape, None, None),
                      spec(p["wg"].shape, "experts", None, "expert_mlp"),
                      spec(p["wu"].shape, "experts", None, "expert_mlp"),
                      spec(p["wd"].shape, "experts", "expert_mlp", None)),
            out_specs=(x_sp, P()),
            check_vma=False,
        )(x, p["router"], p["wg"], p["wu"], p["wd"])

    if cfg.moe.num_shared_experts:
        from repro.models.mlp import apply_mlp
        out = out + apply_mlp(p["shared"], x, cfg)
    return out, aux
