"""DLRM — the paper's centerpiece workload (Fig. 2), partitioned per Fig. 6:
model-parallel sparse embeddings (tables assigned whole to shards, laid out
as one row-sharded slab by core.partitioner) + data-parallel dense MLPs,
with the sparse and dense stages exposed separately for pipelining (T2).

The dense stage joins the bottom MLP's output and the pooled bags by the
configuration's interaction: pairwise dots (``"dot"``), or the low-rank
cross network of DCN V2 (``"dcn"``, MLPerf's DLRM-DCNv2,
``configs/dlrm_dcnv2.py``): ``x0 = concat([bottom, pooled...])``, then
``x = x0 * ((x @ V_l) @ W_l + b_l) + x`` per layer, then the top MLP.

Tables may be row-wise int8/int4 quantized (T3); lookups then fuse
dequantization into the pooling (the kernels/sls Pallas kernel is the TPU
version; the jnp path here is its oracle-equivalent).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.dlrm_paper import DLRMConfig
from repro.core.partitioner import TableAssignment, partition_tables
from repro.core.quantization import quantize_rows
from repro.core.transfer import unpack_indices
from repro.sharding.rules import (Logical, current_ctx, logical_to_spec,
                                  mesh_axis_names, mesh_axis_size)


def make_assignment(cfg: DLRMConfig, num_shards: int,
                    length_aware: bool = True) -> TableAssignment:
    return partition_tables(
        cfg.table_rows, num_shards,
        avg_lookups=cfg.avg_lookups_per_table if length_aware else None,
        embed_dim=cfg.embed_dim)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _mlp_init(key, dims, dtype):
    layers = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        k1, key = jax.random.split(key)
        w = jax.random.normal(k1, (a, b), jnp.float32) / np.sqrt(a)
        layers.append({"w": w.astype(dtype), "b": jnp.zeros((b,), dtype)})
    return layers


def init_dlrm(cfg: DLRMConfig, assignment: TableAssignment, key,
              quantize: bool = False) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.param_dtype)
    k_slab, k_bot, k_top = jax.random.split(key, 3)
    total = assignment.total_rows
    slab = jax.random.normal(k_slab, (total, cfg.embed_dim), jnp.float32)
    slab = slab / np.sqrt(cfg.embed_dim)
    params: Dict[str, Any] = {}
    if quantize and cfg.quant.embedding_bits:
        params["slab_q"] = quantize_rows(slab, cfg.quant.embedding_bits)
    else:
        params["slab"] = slab.astype(dt)
    dims_bot = (cfg.num_dense_features,) + cfg.bottom_mlp
    dims_top = (cfg.top_in_dim,) + cfg.top_mlp
    params["bottom"] = _mlp_init(k_bot, dims_bot, dt)
    params["top"] = _mlp_init(k_top, dims_top, dt)
    if cfg.interaction == "dcn":
        params["cross"] = _cross_init(jax.random.fold_in(key, 3),
                                      cfg.cross_dim, cfg.dcn_low_rank_dim,
                                      cfg.dcn_num_layers, dt)
    return params


def _cross_init(key, d, r, layers, dtype):
    """Low-rank cross layers: V (d, r) with no bias, W (r, d), b (d,)."""
    out = []
    for i in range(layers):
        kv, kw = jax.random.split(jax.random.fold_in(key, i))
        v = jax.random.normal(kv, (d, r), jnp.float32) / np.sqrt(d)
        w = jax.random.normal(kw, (r, d), jnp.float32) / np.sqrt(r)
        out.append({"v": v.astype(dtype), "w": w.astype(dtype),
                    "b": jnp.zeros((d,), dtype)})
    return out


# --------------------------------------------------------------------------
# sparse stage: SLS over the slab (T1)
# --------------------------------------------------------------------------

def _pool_rows(rows, lengths, L):
    """rows (B,T,L,D), lengths (B,T) -> masked bag-sum (B,T,D)."""
    mask = jnp.arange(L)[None, None, :] < lengths[..., None]
    return jnp.sum(rows * mask[..., None], axis=2)


SB_ROWS = 64        # rows whose fp16 scales and biases share one "sb" row


def pack_scale_bias(slab_q):
    """A quantized slab plus ``"sb"`` (ceil(R/64), 128) fp16: row k holds
    the scales of rows 64k..64k+63, then their biases. A lookup then reads
    its scale and bias as one 256-byte row, where two gathers of single
    fp16 elements took about 2.8x the int8 row gather's time on a TPU v5e
    and swung by 7 % from process to process."""
    return {**slab_q, "sb": _sb_rows(slab_q["scale"], slab_q["bias"])}


@jax.jit
def _sb_rows(scale, bias):
    part = lambda x: jnp.pad(x, (0, -x.shape[0] % SB_ROWS)).reshape(
        -1, SB_ROWS)
    return jnp.concatenate([part(scale), part(bias)], axis=1)


def _take_scale_bias(slab_q, idx):
    """Each index's fp16 scale and bias, as float32."""
    if "sb" not in slab_q:
        return (jnp.take(slab_q["scale"], idx, axis=0).astype(jnp.float32),
                jnp.take(slab_q["bias"], idx, axis=0).astype(jnp.float32))
    blk = jnp.take(slab_q["sb"], idx // SB_ROWS, axis=0)        # (..., 128)
    hit = jnp.arange(SB_ROWS) == (idx % SB_ROWS)[..., None]
    pick = lambda x: jnp.sum(jnp.where(hit, x, 0), axis=-1)      # one term
    return (pick(blk[..., :SB_ROWS]).astype(jnp.float32),
            pick(blk[..., SB_ROWS:]).astype(jnp.float32))


def _take_dequant(slab_or_q, idx):
    """Gather rows by global index; fused dequant for quantized slabs."""
    if isinstance(slab_or_q, dict):
        scale, bias = _take_scale_bias(slab_or_q, idx)
        if "q8" in slab_or_q:
            vals = jnp.take(slab_or_q["q8"], idx, axis=0).astype(jnp.float32)
        else:
            q = jnp.take(slab_or_q["q4"], idx, axis=0)
            lo = (q & 0xF).astype(jnp.float32)
            hi = (q >> 4).astype(jnp.float32)
            vals = jnp.stack([lo, hi], axis=-1).reshape(q.shape[:-1] + (-1,))
        return vals * scale[..., None] + bias[..., None]
    return jnp.take(slab_or_q, idx, axis=0)


def _staged_columns(lengths, width: int):
    """The column map of a ``(B, width)`` staging buffer
    (``core/transfer.py``: each item's bags back to back in table order,
    then padding), from lengths (B, T): each column's table, (B, width),
    and whether the column holds a lookup, (B, width)."""
    end = jnp.cumsum(lengths, axis=1)                           # (B, T)
    col = jnp.arange(width)
    done = jnp.sum(end[:, :, None] <= col, axis=1)   # bags ended before col
    return (jnp.minimum(done, lengths.shape[1] - 1),
            col[None, :] < end[:, -1:])


def _sls_staged(slab, offsets, staged, lengths):
    """staged (B, W) ids, lengths (B, T) -> pooled (B, T, D) float32:
    gather the B x W staged rows, zero the dead columns, and sum each
    item's columns table by table: a (T, W) one-hot contraction per item
    at float32 precision."""
    T = lengths.shape[1]
    table, live = _staged_columns(lengths, staged.shape[1])
    rows = _take_dequant(slab, staged + offsets[table])
    rows = jnp.where(live[..., None], rows, 0.0).astype(jnp.float32)
    onehot = (jnp.arange(T)[None, :, None] == table[:, None, :]).astype(
        jnp.float32)                                            # (B, T, W)
    return jnp.einsum("btw,bwd->btd", onehot, rows,
                      precision=jax.lax.Precision.HIGHEST)


def sls_forward(params, cfg: DLRMConfig, assignment: TableAssignment,
                indices, lengths):
    """indices (B,T,L) per-table bag indices, or the ingest's (B,W) staging
    buffer (``core/transfer.py``), lengths (B,T) -> pooled embeddings
    (B,T,D). Sharded over the slab's row axis when a mesh context is
    active (= the paper's cards; psum gathers the sparse results to the
    dense partition, device-to-device)."""
    offsets = jnp.asarray(assignment.table_offset, jnp.int32)
    slab = params.get("slab_q", params.get("slab"))
    ctx = current_ctx()
    rs = mesh_axis_size("table_rows")
    if indices.ndim == 2:
        if ctx is None or rs == 1:
            return _sls_staged(slab, offsets, indices, lengths)
        indices = unpack_indices(indices, lengths, cfg.max_lookups_per_table)
    B, T, L = indices.shape
    gidx = indices + offsets[None, :, None]
    if ctx is None or rs == 1:
        rows = _take_dequant(slab, gidx)
        return _pool_rows(rows, lengths, L).astype(jnp.float32)

    axes = mesh_axis_names("table_rows")
    rows_local = assignment.total_rows // rs

    def body(slab, gidx, lengths):
        # paper Fig. 6: requests are REPLICATED across the sparse (table)
        # shards — each card serves every request for its own tables — and
        # the psum plays the role of gathering sparse results to the dense
        # partition over the switch (ICI), host-free (T9).
        rank = jax.lax.axis_index(axes)
        start = rank * rows_local
        loc = gidx - start
        hit = (loc >= 0) & (loc < rows_local)
        rows = _take_dequant(slab, jnp.clip(loc, 0, rows_local - 1))
        rows = jnp.where(hit[..., None], rows, 0.0)
        pooled = _pool_rows(rows, lengths, L)
        return jax.lax.psum(pooled.astype(jnp.float32), axes)

    spec = lambda *a: logical_to_spec(Logical(*a), ctx.rules, ctx.mesh)
    if isinstance(slab, dict):
        # a shard's rows need not start an "sb" row: gather single scales
        slab = {k: v for k, v in slab.items() if k != "sb"}
        slab_spec = {k: (spec("table_rows", None) if k.startswith("q")
                         else spec("table_rows")) for k in slab}
    else:
        slab_spec = spec("table_rows", None)
    pooled = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(slab_spec, spec(None, None, None), spec(None, None)),
        out_specs=spec(None, None, None), check_vma=False,
    )(slab, gidx, lengths)
    # hand the gathered result to the data-parallel dense partition
    from repro.sharding.rules import shard as _shard
    return _shard(pooled, "batch", None, None)


# --------------------------------------------------------------------------
# dense stage: bottom MLP + interaction + top MLP (data-parallel)
# --------------------------------------------------------------------------

def _mlp_apply(layers, x, final_linear=False):
    for i, l in enumerate(layers):
        x = x @ l["w"] + l["b"]
        if not (final_linear and i == len(layers) - 1):
            x = jax.nn.relu(x)
    return x


def cross_forward(layers, x0):
    """DCN V2 low-rank cross network: x0 (B, d) -> (B, d)."""
    x = x0
    for l in layers:
        x = x0 * ((x @ l["v"]) @ l["w"] + l["b"]) + x
    return x


def dense_forward(params, cfg: DLRMConfig, dense_x, pooled):
    """dense_x (B,13), pooled (B,T,D) -> logits (B,)."""
    bot = _mlp_apply(params["bottom"], dense_x.astype(jnp.float32))
    cat = jnp.concatenate([bot[:, None, :], pooled], axis=1)  # (B,T+1,D)
    if cfg.interaction == "dcn":
        x = cross_forward(params["cross"], cat.reshape(cat.shape[0], -1))
        return _mlp_apply(params["top"], x, final_linear=True)[:, 0]
    Z = jnp.einsum("bid,bjd->bij", cat, cat)
    n = cat.shape[1]
    iu, ju = np.triu_indices(n, k=1)
    inter = Z[:, iu, ju]                                       # (B, n(n-1)/2)
    top_in = jnp.concatenate([bot, inter], axis=1)
    out = _mlp_apply(params["top"], top_in, final_linear=True)
    return out[:, 0]


def dlrm_forward(params, cfg: DLRMConfig, assignment: TableAssignment,
                 dense_x, indices, lengths):
    pooled = sls_forward(params, cfg, assignment, indices, lengths)
    return dense_forward(params, cfg, dense_x, pooled)


def dlrm_loss(params, cfg: DLRMConfig, assignment: TableAssignment, batch):
    logits = dlrm_forward(params, cfg, assignment, batch["dense"],
                          batch["indices"], batch["lengths"])
    y = batch["labels"].astype(jnp.float32)
    loss = jnp.mean(jax.nn.softplus(logits) - y * logits)      # BCE
    return loss, logits
