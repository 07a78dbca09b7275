"""Vocab-parallel embedding + LM head — the paper's T1 partitioning applied
to LM tables: embedding rows are model-parallel ("sparse side" sharded across
devices), activations stay data-parallel, and per-device partial lookups are
combined with a collective ("sparse results gathered to the dense compute").

Also provides the vocab-parallel cross-entropy (never materializes the full
logits — a beyond-paper optimization recorded in EXPERIMENTS §Perf) and a
sharded greedy/top-k for decode.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import VOCAB_PAD_MULT, round_up, softcap
from repro.sharding.rules import (Logical, current_ctx, logical_to_spec,
                                  mesh_axis_names, mesh_axis_size)


def padded_vocab(cfg: ModelConfig) -> int:
    return round_up(cfg.vocab_size, VOCAB_PAD_MULT)


def _spec(ctx, axes, shape):
    return logical_to_spec(Logical(*axes), ctx.rules, ctx.mesh, tuple(shape))


# --------------------------------------------------------------------------
# embedding lookup
# --------------------------------------------------------------------------

def embed_lookup(table, tokens, cfg: ModelConfig):
    """table (Vp, d) row-sharded over rules.vocab; tokens (B,S) int32."""
    ctx = current_ctx()
    vs = mesh_axis_size("vocab")
    if ctx is None or vs == 1:
        out = jnp.take(table, tokens, axis=0)
    else:
        axes = mesh_axis_names("vocab")
        Vp = table.shape[0]
        V_local = Vp // vs

        def body(table, tokens):
            rank = jax.lax.axis_index(axes)
            start = rank * V_local
            local = tokens - start
            hit = (local >= 0) & (local < V_local)
            rows = jnp.take(table, jnp.clip(local, 0, V_local - 1), axis=0)
            rows = jnp.where(hit[..., None], rows, 0)
            return jax.lax.psum(rows, axes)

        t_spec = _spec(ctx, ("vocab", None), table.shape)
        tok_spec = _spec(ctx, ("batch", None), tokens.shape)
        out_spec = _spec(ctx, ("batch", None, None),
                         tokens.shape + (table.shape[1],))
        out = jax.shard_map(
            body, mesh=ctx.mesh, in_specs=(t_spec, tok_spec),
            out_specs=out_spec, check_vma=False)(table, tokens)
    if cfg.embedding_multiplier:
        out = (out.astype(jnp.float32) * cfg.embedding_multiplier).astype(out.dtype)
    return out.astype(jnp.dtype(cfg.activation_dtype))


# --------------------------------------------------------------------------
# LM head: loss without materializing logits
# --------------------------------------------------------------------------

def lm_head_loss(x, table, labels, cfg: ModelConfig,
                 mask: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Vocab-parallel softmax cross-entropy.

    x (B,S,d), table (Vp,d) row-sharded, labels (B,S) int32.
    Returns (mean loss, mean z-term) — z (logsumexp^2) is useful as z-loss.
    """
    ctx = current_ctx()
    vs = mesh_axis_size("vocab")
    V = cfg.vocab_size
    cap = cfg.final_logit_softcap
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    mask = mask.astype(jnp.float32)

    if ctx is None or vs == 1:
        logits = jnp.einsum("bsd,vd->bsv", x, table)
        logits = softcap(logits, cap).astype(jnp.float32)
        logits = jnp.where(jnp.arange(table.shape[0]) < V, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        corr = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        loss = (lse - corr) * mask
        denom = jnp.maximum(mask.sum(), 1.0)
        return loss.sum() / denom, (lse * lse * mask).sum() / denom

    axes = mesh_axis_names("vocab")
    Vp = table.shape[0]
    V_local = Vp // vs

    def body(x, table, labels, mask):
        rank = jax.lax.axis_index(axes)
        start = rank * V_local
        logits = jnp.einsum("bsd,vd->bsv", x, table)
        logits = softcap(logits, cap).astype(jnp.float32)
        valid_col = (jnp.arange(V_local) + start) < V
        logits = jnp.where(valid_col, logits, -1e30)
        # the logsumexp max shift is gradient-free (standard trick) — pmax
        # has no differentiation rule, and needs none here
        m_loc = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
        m = jax.lax.pmax(m_loc, axes)
        se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
        se = jax.lax.psum(se, axes)
        lse = m + jnp.log(se)
        loc = labels - start
        hit = (loc >= 0) & (loc < V_local)
        corr = jnp.take_along_axis(
            logits, jnp.clip(loc, 0, V_local - 1)[..., None], axis=-1)[..., 0]
        corr = jax.lax.psum(jnp.where(hit, corr, 0.0), axes)
        loss = (lse - corr) * mask
        batch_axes = mesh_axis_names("batch")
        # global token count (mask is batch-sharded, vocab-replicated)
        gcount = mask.sum()
        if batch_axes:
            gcount = jax.lax.psum(gcount, batch_axes)
        denom = jnp.maximum(gcount, 1.0)
        tot = loss.sum() / denom
        z = (lse * lse * mask).sum() / denom
        if batch_axes:
            tot = jax.lax.psum(tot, batch_axes)
            z = jax.lax.psum(z, batch_axes)
        return tot, z

    x_spec = _spec(ctx, ("batch", None, None), x.shape)
    t_spec = _spec(ctx, ("vocab", None), table.shape)
    l_spec = _spec(ctx, ("batch", None), labels.shape)
    m_spec = _spec(ctx, ("batch", None), mask.shape)
    loss, z = jax.shard_map(
        body, mesh=ctx.mesh, in_specs=(x_spec, t_spec, l_spec, m_spec),
        out_specs=(P(), P()), check_vma=False)(x, table, labels, mask)
    return loss, z


# --------------------------------------------------------------------------
# LM head: logits / greedy for decode
# --------------------------------------------------------------------------

def lm_head_logits(x, table, cfg: ModelConfig):
    """Full logits (B,S,Vp) — auto-sharded path for small/serving use."""
    logits = jnp.einsum("bsd,vd->bsv", x, table)
    logits = softcap(logits, cfg.final_logit_softcap)
    return jnp.where(jnp.arange(table.shape[0]) < cfg.vocab_size,
                     logits, -jnp.inf)


def sharded_greedy(x, table, cfg: ModelConfig) -> jax.Array:
    """argmax over the vocab-sharded head; x (B,d) -> token ids (B,)."""
    ctx = current_ctx()
    vs = mesh_axis_size("vocab")
    if ctx is None or vs == 1:
        logits = lm_head_logits(x[:, None], table, cfg)[:, 0]
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    axes = mesh_axis_names("vocab")
    Vp = table.shape[0]
    V_local = Vp // vs

    def body(x, table):
        rank = jax.lax.axis_index(axes)
        start = rank * V_local
        logits = jnp.einsum("bd,vd->bv", x, table)
        logits = softcap(logits, cfg.final_logit_softcap).astype(jnp.float32)
        valid = (jnp.arange(V_local) + start) < cfg.vocab_size
        logits = jnp.where(valid, logits, -jnp.inf)
        v_loc = jnp.max(logits, axis=-1)
        i_loc = jnp.argmax(logits, axis=-1).astype(jnp.int32) + start
        v_max = jax.lax.pmax(v_loc, axes)
        # tie-break to the lowest-index winner, matching jnp.argmax
        cand = jnp.where(v_loc >= v_max, i_loc, jnp.iinfo(jnp.int32).max)
        return jax.lax.pmin(cand, axes)

    x_spec = _spec(ctx, ("batch", None), x.shape)
    t_spec = _spec(ctx, ("vocab", None), table.shape)
    out_spec = _spec(ctx, ("batch",), (x.shape[0],))
    return jax.shard_map(body, mesh=ctx.mesh, in_specs=(x_spec, t_spec),
                             out_specs=out_spec, check_vma=False)(x, table)
