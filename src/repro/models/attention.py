"""Attention: GQA/MQA, global/local(sliding-window), logit softcap,
RoPE / M-RoPE, cross-attention, KV caches (full + ring buffer), and a
sequence-sharded decode path (flash partial-softmax merge over the mesh).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.quantization import dense_w8a8, is_quantized_dense
from repro.models.common import (apply_mrope, apply_rope, mk_param, softcap)
from repro.sharding.rules import (current_ctx, logical_to_spec, Logical,
                                  mesh_axis_names, mesh_axis_size, shard)

NEG_INF = -2.3819763e38   # kept finite so masked softmax rows stay NaN-free
PREFILL_Q_CHUNK = 4096    # query-block size for long-prefill chunked attention


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key, cross: bool = False):
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hp = cfg.padded_heads          # TP-divisible head count (>= H)
    p = {
        "wq": mk_param(ks[0], (d, Hp, hd), ("embed", "heads", None), dt),
        "wk": mk_param(ks[1], (d, K, hd), ("embed", "kv_heads", None), dt),
        "wv": mk_param(ks[2], (d, K, hd), ("embed", "kv_heads", None), dt),
        "wo": mk_param(ks[3], (Hp, hd, d), ("heads", None, "embed"), dt),
    }
    if Hp > H and not isinstance(p["wo"], Logical):
        # padded heads' output rows are zero: attention output is exact
        p["wo"] = p["wo"].at[H:].set(0)
    if cfg.qkv_bias:
        p["bq"] = mk_param(ks[4], (Hp, hd), ("heads", None), dt, "zeros")
        p["bk"] = mk_param(ks[5], (K, hd), ("kv_heads", None), dt, "zeros")
        p["bv"] = mk_param(ks[6], (K, hd), ("kv_heads", None), dt, "zeros")
    if cfg.o_bias:
        p["bo"] = mk_param(ks[7], (d,), ("embed",), dt, "zeros")
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, kind: str,
                  dtype=None):
    """Cache pytree for one attention layer. 'local' uses a ring buffer of
    ``window_size`` slots; 'global' holds ``max_len``.

    With cfg.quant.kv_cache_dtype == 'int8' (paper T3 applied to serving),
    K/V store int8 with per-(token, kv-head) symmetric scales — halves the
    memory-bound decode cache traffic."""
    dtype = dtype or jnp.dtype(cfg.activation_dtype)
    slots = min(cfg.window_size, max_len) if kind == "local" else max_len
    shape = (batch, slots, cfg.num_kv_heads, cfg.head_dim)
    seq_ax = None if kind == "local" else "kv_seq"
    axes = ("batch", seq_ax, "kv_heads", None)
    if cfg.quant.kv_cache_dtype == "int8":
        return {
            "k": mk_param(None, shape, axes, jnp.int8, "zeros"),
            "v": mk_param(None, shape, axes, jnp.int8, "zeros"),
            "k_scale": mk_param(None, shape[:3], axes[:3], jnp.float16,
                                "zeros"),
            "v_scale": mk_param(None, shape[:3], axes[:3], jnp.float16,
                                "zeros"),
        }
    return {
        "k": mk_param(None, shape, axes, dtype, "zeros"),
        "v": mk_param(None, shape, axes, dtype, "zeros"),
    }


def _kv_quant(x):
    """x (..., hd) -> (int8 vals, fp16 scale (...,)) symmetric per vector."""
    absmax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                         1e-6)
    scale = absmax / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _kv_dequant(q, scale, dtype):
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def _ring_newest_positions(last, win: int):
    """Per ring slot r, the newest absolute position p <= ``last`` (B,)
    with p % win == r; negative means that slot was never written. The
    ONE ring-layout derivation both the monolithic fill and the chunked
    path share — the token-identity contract needs them to agree."""
    r = jnp.arange(win, dtype=jnp.int32)[None, :]
    last = last[:, None]
    return last - jnp.mod(last - r, win)                   # (B, win)


# --------------------------------------------------------------------------
# projections
# --------------------------------------------------------------------------

def _head_proj(x, w, cfg: ModelConfig):
    """x (B,S,d) @ w (d,H,hd) -> (B,S,H,hd); the quantized form stores the
    head axes flattened ((d, H*hd) int8) and restores them from
    ``cfg.head_dim``."""
    if is_quantized_dense(w):
        y = dense_w8a8(x, w)
        return y.reshape(y.shape[:2] + (-1, cfg.head_dim))
    return jnp.einsum("bsd,dhk->bshk", x, w)


def _project_qkv(p, x, cfg: ModelConfig, positions, kv_x=None, rope: bool = True):
    kv_x = x if kv_x is None else kv_x
    q = _head_proj(x, p["wq"], cfg)
    k = _head_proj(kv_x, p["wk"], cfg)
    v = _head_proj(kv_x, p["wv"], cfg)
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if rope and positions is not None:
        if cfg.rope_mode == "mrope":
            q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _out_proj(p, o):
    if is_quantized_dense(p["wo"]):
        B, S = o.shape[:2]
        y = dense_w8a8(o.reshape(B, S, -1), p["wo"])
    else:
        y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    return shard(y, "batch", "seq", None)


# --------------------------------------------------------------------------
# full attention (train / prefill / encoder)
# --------------------------------------------------------------------------

def _gqa_scores(q, k, cfg: ModelConfig):
    """q (B,S,H,hd), k (B,T,K,hd) -> scores (B,K,G,S,T).

    The MXU accumulates in f32 (preferred_element_type); the materialized
    logits are stored back in the activation dtype — flash-style numerics
    (paper T3: data-type changes for compute). Softmax re-upcasts its
    internals to f32, fused."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    # emit logits in the activation dtype: the MXU accumulates f32
    # internally regardless, and the (B,K,G,S,T) materialization halves
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=q.dtype)
    s = s * (hd ** -0.5)
    return softcap(s, cfg.attn_logit_softcap)


def _gqa_out(probs, v):
    """probs (B,K,G,S,T) fp32, v (B,T,K,hd) -> (B,S,H,hd)."""
    B, K, G, S, T = probs.shape
    o = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return o.reshape(B, S, K * G, -1)


def full_attention(p, x, cfg: ModelConfig, kind: str, positions,
                   kv_valid=None, causal: bool = True, cross_kv=None):
    """Dense attention over a whole sequence.

    kind: 'global' | 'local'. cross_kv: dict(k=,v=) for encoder-decoder
    cross attention (no rope, no causal mask over encoder keys).
    """
    if cross_kv is not None:
        q, _, _ = _project_qkv(p, x, cfg, positions=None, rope=False)
        k, v = cross_kv["k"], cross_kv["v"]
        scores = _gqa_scores(q, k, cfg)
        if kv_valid is not None:
            scores = jnp.where(kv_valid[:, None, None, None, :], scores,
                               jnp.asarray(NEG_INF, scores.dtype))
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        return _out_proj(p, _gqa_out(probs, v)), None

    q, k, v = _project_qkv(p, x, cfg, positions)
    S = x.shape[1]
    qpos = positions if positions.ndim == 2 else positions[0]
    kpos = qpos

    if cfg.attention_impl == "flash_pallas" and causal:
        from repro.kernels.flash_attn.ops import flash_attn
        lens = kv_valid.sum(-1).astype(jnp.int32) if kv_valid is not None \
            else None
        o = flash_attn(q, k, v, lens, causal=True,
                       window=cfg.window_size if kind == "local" else 0,
                       softcap=cfg.attn_logit_softcap or 0.0)
        o = shard(o, "batch", "seq", "heads", None)
        return _out_proj(p, o), (k, v)

    def core(q_blk, qpos_blk):
        """Attention of a query block against the full K/V."""
        mask = jnp.ones((q_blk.shape[0], q_blk.shape[1], S), bool)
        if causal:
            mask &= qpos_blk[:, :, None] >= kpos[:, None, :]
        if kind == "local":
            mask &= qpos_blk[:, :, None] - kpos[:, None, :] < cfg.window_size
        if kv_valid is not None:
            mask &= kv_valid[:, None, :]
        scores = _gqa_scores(q_blk, k, cfg)
        scores = jnp.where(mask[:, None, None, :, :], scores,
                           jnp.asarray(NEG_INF, scores.dtype))
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        o_blk = _gqa_out(probs, v)
        return shard(o_blk, "batch", "seq", "heads", None)

    if S > 2 * PREFILL_Q_CHUNK and S % PREFILL_Q_CHUNK == 0 \
            and mesh_axis_size("seq") == 1:
        # long prefill: scan query blocks so only one (B,K,G,Sq,T) score
        # block is ever live (peak VMEM/HBM control; traffic unchanged).
        # Skipped under sequence sharding: the shard itself bounds the peak
        # and the chunk reshapes would force per-chunk resharding.
        nblk = S // PREFILL_Q_CHUNK
        qb = jnp.moveaxis(q.reshape((q.shape[0], nblk, PREFILL_Q_CHUNK)
                                    + q.shape[2:]), 1, 0)
        pb = jnp.moveaxis(qpos.reshape(qpos.shape[0], nblk,
                                       PREFILL_Q_CHUNK), 1, 0)
        _, ob = jax.lax.scan(lambda c, inp: (c, core(*inp)), None, (qb, pb))
        o = jnp.moveaxis(ob, 0, 1).reshape(q.shape)
    else:
        o = core(q, qpos)
    return _out_proj(p, o), (k, v)


def fill_cache_from_prefill(cache, k, v, kind: str, cfg: ModelConfig,
                            kv_valid=None):
    """Write prefill K/V into the cache (ring layout for local layers).

    ``kv_valid`` (B, S) marks the real tokens of each padded row. The
    local ring keeps, per row, the LAST ``min(window, length)`` real
    positions at their ring slots — a length-aware fill. (The old fill
    kept the last ``window`` positions of the PADDED sequence, so a
    short prompt in a long bucket parked padding junk in the ring —
    attended by decode once ``pos`` crossed the window. The chunked-path
    identity tests pinned the fix.)"""
    S = k.shape[1]
    slots = cache["k"].shape[1]
    quant = "k_scale" in cache
    pairs = [("k", k), ("v", v)]
    if quant:
        kq, ks = _kv_quant(k)
        vq, vs = _kv_quant(v)
        pairs = [("k", kq), ("v", vq), ("k_scale", ks), ("v_scale", vs)]
    out = {}
    if kind == "local":
        B = k.shape[0]
        lengths = (kv_valid.sum(-1).astype(jnp.int32) if kv_valid is not None
                   else jnp.full((B,), S, jnp.int32))
        p_r = _ring_newest_positions(lengths - 1, slots)       # (B, slots)
        idx = jnp.clip(p_r, 0, S - 1)
        written = p_r >= 0
        for name, val in pairs:
            tail = (1,) * (val.ndim - 2)
            g = jnp.take_along_axis(val, idx.reshape(idx.shape + tail),
                                    axis=1)
            g = jnp.where(written.reshape(written.shape + tail), g, 0)
            out[name] = g.astype(cache[name].dtype)
        return out
    for name, val in pairs:
        start = (0,) * cache[name].ndim
        out[name] = jax.lax.dynamic_update_slice(
            cache[name], val.astype(cache[name].dtype), start)
    return out


# --------------------------------------------------------------------------
# chunked prefill (a block of prompt tokens against a live cache)
# --------------------------------------------------------------------------

def chunk_prefill_attention(p, x, cache, pos, cfg: ModelConfig, kind: str):
    """One prompt chunk per GROUP ROW against the live full-batch cache:
    x (P,C,d) holds the tick's chunk tokens (P = padded group size, a
    subset of the cache's slot batch), row j sitting at absolute offset
    ``start[j]``. ``pos`` is ``(slots, start, write_pos, lengths)``
    (``lengths[j]`` = real tokens in row j's chunk; 0 marks a padded
    row). Global attention:

    - chunk K/V scatters into cache rows ``slots[j]`` at positions
      ``write_pos[j] + 0..C-1``. The update is O(P x C) on the (donated)
      cache, so per-chunk cache traffic matches a decode step — NOT a
      whole-cache copy. Padded rows carry ``write_pos = max_len``; their
      out-of-bounds scatter indices drop, so a duplicated pad slot can
      never clobber a real row.
    - queries then attend their own updated cache row: key j is visible
      to chunk query i iff j <= start + i — exactly the mask a
      monolithic prefill applies at those rows, so iterating chunks is
      prefix-consistent with monolithic prefill.

    Local (sliding-window) attention — the ring-buffer chunk contract
    (PR 5): the ring holds only the last ``window`` keys, so queries
    cannot attend a post-write ring (writing the chunk may evict keys
    the chunk's own early queries still need). Instead:

    - queries attend the PRE-chunk ring (positions ``start-window`` ..
      ``start-1`` at their ring slots, masked to the written window)
      concatenated with the in-chunk keys (causal, window-limited) —
      exactly the key set a monolithic sliding-window prefill exposes,
    - chunk K/V then scatters at ring offsets ``(start + i) % window``,
      keeping only each ring slot's LAST real write (positions past
      ``lengths[j]`` and intra-chunk evictions route out of bounds and
      drop), so the post-chunk ring again holds the newest ``window``
      real positions.

    Returns (y (P,C,d), new full cache)."""
    if kind not in ("global", "local"):
        raise ValueError("chunked prefill supports global and local "
                         f"attention, got {kind!r}")
    slots, start, write_pos, lengths = pos
    P, C = x.shape[0], x.shape[1]
    slots = jnp.asarray(slots, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    write_pos = jnp.asarray(write_pos, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    pos_bc = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    if cfg.rope_mode == "mrope":
        positions = jnp.broadcast_to(pos_bc[None], (3, P, C))
    else:
        positions = pos_bc
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)

    S = cache["k"].shape[1]
    quant = "k_scale" in cache

    if kind == "local":
        return _chunk_prefill_local(p, q, k_new, v_new, cache, slots, start,
                                    write_pos, lengths, pos_bc, cfg, x.dtype)

    widx = write_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]

    def write_chunk(c, new):
        return c.at[slots[:, None], widx].set(new.astype(c.dtype),
                                              mode="drop")

    new_cache = {}
    if quant:
        kq, ks = _kv_quant(k_new)
        vq, vs = _kv_quant(v_new)
        for name, val in (("k", kq), ("v", vq),
                          ("k_scale", ks), ("v_scale", vs)):
            new_cache[name] = write_chunk(cache[name], val)
        ck = _kv_dequant(new_cache["k"][slots],
                         new_cache["k_scale"][slots], x.dtype)
        cv = _kv_dequant(new_cache["v"][slots],
                         new_cache["v_scale"][slots], x.dtype)
    else:
        for name, val in (("k", k_new), ("v", v_new)):
            new_cache[name] = write_chunk(cache[name], val)
        # gather only the P group rows for attention (padded rows whose
        # writes dropped read stale chunk keys — their output is garbage
        # and the engine discards it)
        ck, cv = new_cache["k"][slots], new_cache["v"][slots]

    # causal over the absolute positions: key j visible to chunk query i
    # iff j <= start + i (cache rows past the written prefix are masked,
    # so stale slots can never leak into a chunk's softmax)
    idx = jnp.arange(S, dtype=jnp.int32)
    mask = idx[None, None, :] <= pos_bc[:, :, None]          # (P,C,S)
    scores = _gqa_scores(q, ck, cfg)                         # (P,K,G,C,S)
    scores = jnp.where(mask[:, None, None, :, :], scores,
                       jnp.asarray(NEG_INF, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    o = _gqa_out(probs, cv)
    o = shard(o, "batch", "seq", "heads", None)
    return _out_proj(p, o), new_cache


def _chunk_prefill_local(p, q, k_new, v_new, cache, slots, start, write_pos,
                         lengths, pos_bc, cfg: ModelConfig, dtype):
    """Local-attention half of ``chunk_prefill_attention`` (see there).
    ``q``/``k_new``/``v_new`` are the already-projected chunk tensors."""
    P, C = pos_bc.shape
    B = cache["k"].shape[0]
    win = cache["k"].shape[1]
    quant = "k_scale" in cache

    # ring write: keep, per ring slot, only the LAST real write of this
    # chunk (j >= lengths - win), and only real tokens (j < lengths);
    # everything else routes out of bounds and drops. Padded rows
    # (lengths == 0) additionally route their batch index out of bounds,
    # so a duplicated pad slot can never clobber a real row.
    j = jnp.arange(C, dtype=jnp.int32)[None, :]
    keep = (j < lengths[:, None]) & (j >= lengths[:, None] - win)
    rows = jnp.where(lengths > 0, slots, B)
    widx = jnp.where(keep, jnp.mod(write_pos[:, None] + j, win), win)

    def ring_write(c, new):
        return c.at[rows[:, None], widx].set(new.astype(c.dtype),
                                             mode="drop")

    new_cache = {}
    if quant:
        kq, ks = _kv_quant(k_new)
        vq, vs = _kv_quant(v_new)
        for name, val in (("k", kq), ("v", vq),
                          ("k_scale", ks), ("v_scale", vs)):
            new_cache[name] = ring_write(cache[name], val)
        ring_k = _kv_dequant(cache["k"][slots], cache["k_scale"][slots],
                             dtype)
        ring_v = _kv_dequant(cache["v"][slots], cache["v_scale"][slots],
                             dtype)
        ck_new = _kv_dequant(kq, ks, dtype)
        cv_new = _kv_dequant(vq, vs, dtype)
    else:
        for name, val in (("k", k_new), ("v", v_new)):
            new_cache[name] = ring_write(cache[name], val)
        ring_k, ring_v = cache["k"][slots], cache["v"][slots]
        ck_new, cv_new = k_new, v_new

    # pre-chunk ring slot r holds absolute position p_r = the newest
    # p <= start-1 with p % win == r (negative -> never written); chunk
    # query i (absolute q_i = start+i) sees it iff q_i - p_r < win
    p_r = _ring_newest_positions(start - 1, win)             # (P,win)
    ring_mask = (p_r[:, None, :] >= 0) \
        & (pos_bc[:, :, None] - p_r[:, None, :] < win)       # (P,C,win)
    # in-chunk keys: causal + window over the chunk-relative offsets
    i = jnp.arange(C, dtype=jnp.int32)
    chunk_mask = (i[:, None] >= i[None, :]) \
        & (i[:, None] - i[None, :] < win)                    # (C,C)
    chunk_mask = jnp.broadcast_to(chunk_mask[None], (P, C, C))

    ck = jnp.concatenate([ring_k, ck_new], axis=1)           # (P,win+C,..)
    cv = jnp.concatenate([ring_v, cv_new], axis=1)
    mask = jnp.concatenate([ring_mask, chunk_mask], axis=2)  # (P,C,win+C)
    scores = _gqa_scores(q, ck, cfg)                         # (P,K,G,C,·)
    scores = jnp.where(mask[:, None, None, :, :], scores,
                       jnp.asarray(NEG_INF, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    o = _gqa_out(probs, cv)
    o = shard(o, "batch", "seq", "heads", None)
    return _out_proj(p, o), new_cache


# --------------------------------------------------------------------------
# decode (single new token against a cache)
# --------------------------------------------------------------------------

def _decode_positions(pos, B: int):
    """``pos`` scalar or per-slot (B,) -> int32 (B,)."""
    pos = jnp.asarray(pos, jnp.int32)
    return pos if pos.ndim == 1 else jnp.full((B,), pos, jnp.int32)


def decode_attend(p, x, cache, pos, cfg: ModelConfig, kind: str):
    """Attention of one new token per row against the cache as it stands,
    read-only, plus the token's own K/V as one extra key.

    x (B,1,d); pos int32 scalar OR per-sequence (B,) vector (#tokens
    already in each slot's cache — continuous batching decodes slots at
    different positions). The cache keys visible to row b are exactly
    those a write-then-attend step would see besides the new token:
    global, positions ``< pos``; local ring, slots ``< pos`` until the
    ring is full, then every slot but ``pos % window`` (it holds
    ``pos - window``, which the new token evicts). Returns (y (B,1,d),
    rows): ``rows`` holds the token's K/V in the cache's dtype (int8
    values plus fp16 scales for an int8 cache), shaped like a cache with
    one position, for ``write_decode_rows``. Dispatches to the
    sequence-sharded path when the mesh shards the cache sequence axis."""
    B = x.shape[0]
    per_slot = jnp.ndim(pos) == 1
    pos_b = _decode_positions(pos, B)
    if cfg.rope_mode == "mrope":
        positions = jnp.broadcast_to(pos_b[None, :, None], (3, B, 1))
    else:
        positions = pos_b[:, None]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)

    if "k_scale" in cache:
        kq, ks = _kv_quant(k_new)
        vq, vs = _kv_quant(v_new)
        rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        # the new key as a later step will read it back
        k_self = _kv_dequant(kq, ks, x.dtype)
        v_self = _kv_dequant(vq, vs, x.dtype)
        ck = _kv_dequant(cache["k"], cache["k_scale"], x.dtype)
        cv = _kv_dequant(cache["v"], cache["v_scale"], x.dtype)
    else:
        rows = {"k": k_new.astype(cache["k"].dtype),
                "v": v_new.astype(cache["v"].dtype)}
        k_self, v_self = rows["k"], rows["v"]
        ck, cv = cache["k"], cache["v"]

    # sequence-sharded fast path: scalar-position batches only (it has no
    # per-slot offsets); serving's continuous batching takes the general
    # path below, which is correct under any mesh
    if kind == "global" and mesh_axis_size("kv_seq") > 1 \
            and "k_scale" not in cache and not per_slot:
        o = _decode_seq_sharded(q, k_self, v_self, cache, pos_b[0], cfg)
        return _out_proj(p, o), rows

    slots = cache["k"].shape[1]
    idx = jnp.arange(slots)[None, :]
    before = idx < pos_b[:, None]
    if kind == "local":
        # ring buffer: once full, every slot but the one the new token
        # overwrites holds one of the last window - 1 tokens
        before = jnp.where(pos_b[:, None] >= slots,
                           idx != jnp.mod(pos_b, slots)[:, None], before)
    s_cache = _gqa_scores(q, ck, cfg)                     # (B,K,G,1,slots)
    s_cache = jnp.where(before[:, None, None, None, :], s_cache,
                        jnp.asarray(NEG_INF, s_cache.dtype))
    s_cache = s_cache.astype(jnp.float32)
    s_self = _gqa_scores(q, k_self, cfg).astype(jnp.float32)   # (...,1)
    m = jnp.maximum(jnp.max(s_cache, axis=-1, keepdims=True), s_self)
    p_cache = jnp.exp(s_cache - m)
    p_self = jnp.exp(s_self - m)
    denom = jnp.sum(p_cache, axis=-1, keepdims=True) + p_self
    # both halves summed in float32, cast once
    o = (jnp.einsum("bkgst,btkd->bskgd", (p_cache / denom).astype(cv.dtype),
                    cv, preferred_element_type=jnp.float32)
         + jnp.einsum("bkgst,btkd->bskgd", p_self / denom,
                      v_self.astype(jnp.float32)))
    o = o.reshape(B, 1, -1, o.shape[-1]).astype(cv.dtype)
    return _out_proj(p, o), rows


def write_decode_rows(cache, rows, pos, kind: str, active=None):
    """Write each row's new K/V (``rows`` from ``decode_attend``) into
    ``cache`` at its position — the ring offset ``pos % window`` for a
    local layer — as one in-place scatter per leaf. Leaves may carry
    leading layer axes (a scanned stack of caches, rows stacked the same
    way). ``active`` (B,) bool marks the rows really decoding: the others
    (free or mid-chunked-prefill rows riding the static-shape dispatch)
    write out of range and drop, so their cache rows stay bit for bit."""
    lead = cache["k"].ndim - 4
    B, slots = cache["k"].shape[lead:lead + 2]
    pos_b = _decode_positions(pos, B)
    at = jnp.mod(pos_b, slots) if kind == "local" else pos_b
    if active is not None:
        at = jnp.where(jnp.asarray(active, bool), at, slots)
    where = (slice(None),) * lead + (jnp.arange(B), at)
    new_row = (slice(None),) * lead + (slice(None), 0)
    return {name: c.at[where].set(rows[name][new_row].astype(c.dtype),
                                  mode="drop")
            for name, c in cache.items()}


def decode_attention(p, x, cache, pos, cfg: ModelConfig, kind: str,
                     active=None):
    """One decode step of one layer: ``decode_attend`` then
    ``write_decode_rows``. Returns (y (B,1,d), new_cache). The model's
    decode step runs the two halves apart, writing every layer's rows
    after the layer scan."""
    y, rows = decode_attend(p, x, cache, pos, cfg, kind)
    return y, write_decode_rows(cache, rows, pos, kind, active)


def merge_partials(o_parts, m_parts, l_parts, axis=0):
    """Merge flash-attention partials: o_i normalized outputs, m_i row maxes,
    l_i row sums -> combined softmax output. Shapes broadcast over ``axis``."""
    m = jnp.max(m_parts, axis=axis, keepdims=True)
    alpha = jnp.exp(m_parts - m)
    l = jnp.sum(l_parts * alpha, axis=axis)
    o = jnp.sum(o_parts * (l_parts * alpha)[..., None], axis=axis)
    return o / l[..., None]


def _decode_seq_sharded(q, k_self, v_self, cache, pos, cfg: ModelConfig):
    """Decode attention with the KV cache sharded along sequence on the mesh
    (paper T1/T9 analogue: partial results merged device-to-device, host-free).

    Each shard computes a flash partial (o, m, l) over its read-only
    positions ``< pos``; the shards' partials merge with a tiny psum
    instead of gathering the cache, and the new token (``k_self`` /
    ``v_self``) joins that merge as one more partial. Returns o
    (B,1,H,hd); the caller writes the new row.
    """
    ctx = current_ctx()
    mesh = ctx.mesh
    seq_axes = mesh_axis_names("kv_seq")
    n_shards = mesh_axis_size("kv_seq")
    S = cache["k"].shape[1]
    S_local = S // n_shards

    cache_spec = logical_to_spec(Logical("batch", "kv_seq", "kv_heads", None),
                                 ctx.rules, mesh, cache["k"].shape)
    qkv_spec = logical_to_spec(Logical("batch", None, "kv_heads", None),
                               ctx.rules, mesh, k_self.shape)
    q_spec = logical_to_spec(Logical("batch", None, "heads", None),
                             ctx.rules, mesh, q.shape)

    def body(q, k_self, v_self, ck, cv, pos):
        start = jax.lax.axis_index(seq_axes) * S_local
        valid = jnp.arange(S_local) < jnp.clip(pos - start, 0, S_local)
        scores = _gqa_scores(q, ck, cfg)                  # (B,K,G,1,S_local)
        scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
        m = jnp.max(scores, axis=-1)                       # (B,K,G,1)
        # guard fully-masked shards
        m_safe = jnp.where(jnp.any(valid), m, NEG_INF)
        p_ = jnp.exp(scores - m_safe[..., None])
        p_ = jnp.where(valid[None, None, None, None, :], p_, 0.0)
        l = jnp.sum(p_, axis=-1)
        o = jnp.einsum("bkgst,btkd->bkgsd", p_.astype(cv.dtype), cv)
        # merge across shards: o is the UNnormalized partial (sum of
        # exp(s - m_local) * v), so rescale by exp(m_local - M) only
        M = jax.lax.pmax(m_safe, seq_axes)
        w = jnp.exp(m_safe - M)
        o = jax.lax.psum(o.astype(jnp.float32) * w[..., None], seq_axes)
        lsum = jax.lax.psum(l * w, seq_axes)
        o = o / jnp.maximum(lsum[..., None], 1e-30)
        # the new token: a one-key partial (its own value, weight 1)
        s_self = _gqa_scores(q, k_self, cfg)[..., 0].astype(jnp.float32)
        v_b = jnp.swapaxes(v_self, 1, 2)[:, :, None]       # (B,K,1,1,hd)
        o = merge_partials(
            jnp.stack([o, jnp.broadcast_to(v_b, o.shape).astype(o.dtype)]),
            jnp.stack([M.astype(jnp.float32), s_self]),
            jnp.stack([lsum, jnp.ones_like(lsum)]))
        B, K, G, S1, hd = o.shape
        o = jnp.swapaxes(o, 1, 3).reshape(B, S1, K * G, hd)
        return o.astype(q.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, qkv_spec, qkv_spec, cache_spec, cache_spec, P()),
        out_specs=q_spec,
        check_vma=False,
    )(q, k_self, v_self, cache["k"], cache["v"], pos)
