"""Model assembly: scan-over-superblocks LM covering all assigned families
(dense / MoE / SSM / hybrid / enc-dec / VLM) plus the XLM-R encoder.

Layer stacks are expressed as a repeating superblock ``unit`` scanned
``repeats`` times plus an unrolled ``tail`` (e.g. recurrentgemma:
(rec, rec, local) x 12 + (rec, rec)). Params/caches for the unit are tuples
(one entry per position) of stacked pytrees with leading dim = repeats.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as blk
from repro.models.common import (VOCAB_PAD_MULT, apply_norm, init_norm,
                                 mk_param, round_up, stacked_init)
from repro.models import attention as attn_mod
from repro.sharding import vocab as vocab_mod
from repro.sharding.rules import shard


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.param_dtype)
    Vp = vocab_mod.padded_vocab(cfg)
    unit, repeats, tail = cfg.scan_plan()
    ks = jax.random.split(key, 8)

    dec_kind = lambda k: "decoder" if cfg.encdec is not None else k
    params: Dict[str, Any] = {
        "embed": mk_param(ks[0], (Vp, cfg.d_model), ("vocab", "embed"), dt),
        "scan": tuple(
            stacked_init(functools.partial(init_unit_pos, cfg, dec_kind(k)),
                         jax.random.fold_in(ks[1], i), repeats)
            for i, k in enumerate(unit)),
        "tail": tuple(
            blk.init_block(cfg, dec_kind(k), jax.random.fold_in(ks[2], i))
            for i, k in enumerate(tail)),
        "final_norm": init_norm(ks[3], cfg.d_model, cfg.norm_type, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = mk_param(ks[4], (Vp, cfg.d_model),
                                     ("vocab", "embed"), dt)
    if cfg.encdec is not None:
        params["enc_scan"] = (stacked_init(
            functools.partial(init_unit_pos, cfg, "global"),
            ks[5], cfg.encdec.encoder_layers),)
        params["enc_final_norm"] = init_norm(ks[6], cfg.d_model,
                                             cfg.norm_type, dt)
    return params


def init_unit_pos(cfg: ModelConfig, kind: str, key):
    return blk.init_block(cfg, kind, key)


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    """Cache pytree matching the scan/tail structure."""
    from repro.sharding.rules import Logical, in_spec_mode
    unit, repeats, tail = cfg.scan_plan()
    dec_kind = lambda k: "decoder" if cfg.encdec is not None else k

    def stack(kind):
        one = blk.init_block_cache(cfg, dec_kind(kind), batch, max_len, dtype)
        if in_spec_mode():
            return jax.tree.map(lambda l: l.prepend(None), one,
                                is_leaf=lambda x: isinstance(x, Logical))
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (repeats,) + l.shape), one)

    caches = {
        "scan": tuple(stack(k) for k in unit),
        "tail": tuple(blk.init_block_cache(cfg, dec_kind(k), batch, max_len,
                                           dtype) for k in tail),
    }
    return caches


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _default_positions(cfg: ModelConfig, B: int, S: int, offset=0):
    pos = jnp.arange(S, dtype=jnp.int32)[None] + offset
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.rope_mode == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def _run_stack(params_scan, params_tail, x, cfg: ModelConfig, kinds_unit,
               kinds_tail, *, mode, positions=None, caches=None, pos=None,
               kv_valid=None, cross_kv=None, cross_valid=None,
               causal=True, remat=False, active=None):
    """Scan the superblock unit, then the unrolled tail. In decode mode
    the scan carries attention caches read-only and stacks only the new
    K/V rows; they are written into the caches after the loop, one
    scatter per leaf for all scanned layers."""
    n_pos = len(kinds_unit)
    aux0 = jnp.zeros((), jnp.float32)

    def unit_body(carry, xs):
        x, aux = carry
        p_unit = xs["p"]
        c_unit = xs.get("c")
        ck_unit = xs.get("ck")      # cross-kv per layer (enc-dec)
        new_caches = []
        for i, kind in enumerate(kinds_unit):
            x, nc, aux = blk.apply_block(
                p_unit[i], x, cfg, kind, mode=mode, positions=positions,
                cache=None if c_unit is None else c_unit[i], pos=pos,
                kv_valid=kv_valid,
                cross_kv=None if ck_unit is None else ck_unit[i],
                cross_valid=cross_valid, causal=causal, aux=aux,
                active=active)
            new_caches.append(nc)
        ys = tuple(new_caches) if mode != "full" else None
        return (x, aux), ys

    body = unit_body
    if remat:
        body = jax.checkpoint(
            unit_body, policy=jax.checkpoint_policies.nothing_saveable)

    xs = {"p": params_scan}
    if caches is not None and mode != "full":
        xs["c"] = caches["scan"]
    if cross_kv is not None:
        xs["ck"] = cross_kv["scan"]
    (x, aux), scan_caches = jax.lax.scan(body, (x, aux0), xs)

    tail_caches = []
    for i, kind in enumerate(kinds_tail):
        c = None if caches is None else caches["tail"][i]
        ck = None if cross_kv is None else cross_kv["tail"][i]
        x, nc, aux = blk.apply_block(
            params_tail[i], x, cfg, kind, mode=mode, positions=positions,
            cache=c, pos=pos, kv_valid=kv_valid, cross_kv=ck,
            cross_valid=cross_valid, causal=causal, aux=aux, active=active)
        tail_caches.append(nc)

    new_caches = None
    if mode == "decode":
        scan_caches = tuple(
            blk.write_decode(c, new, kind, pos, active)
            for c, new, kind in zip(caches["scan"], scan_caches, kinds_unit))
        tail_caches = [
            blk.write_decode(c, new, kind, pos, active)
            for c, new, kind in zip(caches["tail"], tail_caches, kinds_tail)]
    if mode != "full":
        new_caches = {"scan": scan_caches, "tail": tuple(tail_caches)}
    return x, new_caches, aux


def _embed_inputs(params, cfg: ModelConfig, batch: Dict[str, Any]):
    if cfg.input_kind == "embeddings" and "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.activation_dtype))
        if cfg.embedding_multiplier:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        return x
    return vocab_mod.embed_lookup(params["embed"], batch["tokens"], cfg)


def encode(params, cfg: ModelConfig, enc_inputs, enc_valid=None):
    """Encoder stack (enc-dec archs): enc_inputs (B,T,d) stub embeddings."""
    x = enc_inputs.astype(jnp.dtype(cfg.activation_dtype))
    B, T, _ = x.shape
    positions = _default_positions(cfg, B, T)
    x, _, _ = _run_stack(params["enc_scan"], (), x, cfg, ("global",), (),
                         mode="full", positions=positions, kv_valid=enc_valid,
                         causal=False)
    return apply_norm(params["enc_final_norm"], x, cfg.norm_type, cfg.norm_eps)


def build_cross_kv(params, cfg: ModelConfig, enc_hidden):
    """Per-decoder-layer cross K/V from encoder output (prefill-time)."""
    unit, repeats, tail = cfg.scan_plan()

    def one(p_block):
        pa = p_block["xattn"]
        k = jnp.einsum("btd,dhk->bthk", enc_hidden, pa["wk"])
        v = jnp.einsum("btd,dhk->bthk", enc_hidden, pa["wv"])
        if "bk" in pa:
            k = k + pa["bk"]
            v = v + pa["bv"]
        return {"k": k, "v": v}

    scan_ck = tuple(jax.vmap(one)(params["scan"][i]) for i in range(len(unit)))
    tail_ck = tuple(one(params["tail"][i]) for i in range(len(tail)))
    return {"scan": scan_ck, "tail": tail_ck}


def forward(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            mode: str = "full", caches=None, pos=None, kv_valid=None,
            remat: bool = False, active=None):
    """Returns (hidden (B,S,d), new_caches, aux).

    batch: {'tokens' (B,S)} or {'embeds' (B,S,d)}; enc-dec additionally
    {'enc_embeds' (B,T,d)} (mode full/prefill) or precomputed cross-kv in
    ``caches['cross']`` for decode.
    """
    unit, repeats, tail = cfg.scan_plan()
    x = _embed_inputs(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    x = shard(x, "batch", "seq", None)

    cross_kv = None
    cross_valid = None
    if cfg.encdec is not None:
        if "enc_embeds" in batch:
            enc_hidden = encode(params, cfg, batch["enc_embeds"],
                                batch.get("enc_valid"))
            cross_kv = build_cross_kv(params, cfg, enc_hidden)
        else:
            cross_kv = (caches or {}).get("cross")
        cross_valid = batch.get("enc_valid")

    positions = batch.get("positions")
    if positions is None and mode not in ("decode", "chunk"):
        # decode/chunk compute their positions from ``pos`` (per-row
        # cache offsets) inside the attention layer
        positions = _default_positions(cfg, B, S)

    x, new_caches, aux = _run_stack(
        params["scan"], params["tail"], x, cfg, unit, tail, mode=mode,
        positions=positions, caches=caches, pos=pos, kv_valid=kv_valid,
        cross_kv=cross_kv, cross_valid=cross_valid,
        causal=(cfg.family != "encoder"), remat=remat, active=active)

    x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    if cfg.encdec is not None and new_caches is not None and cross_kv is not None:
        new_caches["cross"] = cross_kv
    return x, new_caches, aux


def head_table(params, cfg: ModelConfig):
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


def loss_fn(params, cfg: ModelConfig, batch, *, remat=False):
    """Causal-LM loss (vocab-parallel when a mesh context is active)."""
    x, _, aux = forward(params, cfg, batch, mode="full", remat=remat)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    loss, z = vocab_mod.lm_head_loss(x, head_table(params, cfg), labels, cfg,
                                     mask)
    total = loss + 1e-4 * z + 1e-2 * aux
    return total, {"xent": loss, "z": z, "aux": aux}


def prefill(params, cfg: ModelConfig, batch, max_len: int, kv_valid=None):
    """Run the prompt, fill caches; returns (last_hidden (B,d), caches)."""
    B = (batch.get("tokens") if "tokens" in batch else batch["embeds"]).shape[0]
    caches = init_caches(cfg, B, max_len)
    x, caches, _ = forward(params, cfg, batch, mode="prefill", caches=caches,
                           kv_valid=kv_valid)
    return x[:, -1], caches


def chunk_prefill_step(params, cfg: ModelConfig, tokens, caches, slots,
                       start, write_pos, lengths):
    """Run one prompt chunk per group row against the live full-batch
    caches: tokens (P,C) for cache rows ``slots`` (P,) at absolute
    offsets ``start`` (P,) — row j covers positions
    start[j]..start[j]+C-1, of which the first ``lengths[j]`` are real
    (``lengths == 0`` marks a padded group row). Global K/V scatters at
    ``write_pos[j]`` (pass max_len to park a padded row: its
    out-of-bounds writes drop) and queries attend the whole written
    prefix; local rings write at ring offsets; SSM / RG-LRU blocks seed
    their recurrence from the entering per-slot state and scatter the
    exit state back — so iterating chunks is prefix-consistent with a
    monolithic prefill for EVERY block pattern.
    Returns (hidden (P,C,d), new full caches)."""
    x, caches, _ = forward(params, cfg, {"tokens": tokens}, mode="chunk",
                           caches=caches,
                           pos=(slots, start, write_pos, lengths))
    return x, caches


def decode_step(params, cfg: ModelConfig, tokens, caches, pos, active=None):
    """One decode step: tokens (B,1) [or embeds (B,1,d)] at position ``pos``.
    ``active`` (B,) bool freezes the per-slot state of rows that are not
    really decoding (free / mid-chunked-prefill rows riding the
    static-shape dispatch). Returns (last hidden (B,d), new caches)."""
    batch = {"tokens": tokens} if tokens.ndim == 2 else {"embeds": tokens}
    x, caches, _ = forward(params, cfg, batch, mode="decode", caches=caches,
                           pos=pos, active=active)
    return x[:, -1], caches


def greedy_next(params, cfg: ModelConfig, hidden):
    """hidden (B,d) -> next token ids (B,)."""
    return vocab_mod.sharded_greedy(hidden, head_table(params, cfg), cfg)
