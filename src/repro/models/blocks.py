"""Residual blocks: one init/apply pair per block kind, with a uniform
(x, cache) -> (x', cache') interface so the model-level scan can mix kinds.

Kinds: 'global'/'local' attention (+MLP or MoE), 'ssm' (Mamba2 mixer only),
'recurrent' (RG-LRU + MLP), 'decoder' (whisper: self-attn + cross-attn + MLP).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, CHUNKABLE_KINDS,
                                RECURRENT, SSM, ModelConfig)
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.common import apply_norm, init_norm


def _norm(cfg, key):
    return init_norm(key, cfg.d_model, cfg.norm_type, jnp.dtype(cfg.param_dtype))


def init_block(cfg: ModelConfig, kind: str, key):
    ks = jax.random.split(key, 8)
    p = {"pre_norm": _norm(cfg, ks[0])}
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        p["attn"] = attn.init_attention(cfg, ks[1])
    elif kind == SSM:
        p["ssm"] = ssm_mod.init_ssm(cfg, ks[1])
        return p                                   # mamba block: mixer only
    elif kind == RECURRENT:
        p["rec"] = rglru_mod.init_rglru(cfg, ks[1])
    elif kind == "decoder":
        p["attn"] = attn.init_attention(cfg, ks[1])
        p["xattn_norm"] = _norm(cfg, ks[5])
        p["xattn"] = attn.init_attention(cfg, ks[6])
    else:
        raise ValueError(kind)
    if cfg.post_attn_norm:
        p["post_norm"] = _norm(cfg, ks[2])
    p["pre_mlp_norm"] = _norm(cfg, ks[3])
    if cfg.moe is not None:
        p["moe"] = moe_mod.init_moe(cfg, ks[4])
    else:
        p["mlp"] = mlp_mod.init_mlp(cfg, ks[4])
    if cfg.post_attn_norm:
        p["post_mlp_norm"] = _norm(cfg, ks[7])
    return p


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype=None):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        return attn.init_kv_cache(cfg, batch, max_len, kind, dtype)
    if kind == SSM:
        return ssm_mod.init_ssm_cache(cfg, batch, dtype)
    if kind == RECURRENT:
        return rglru_mod.init_rglru_cache(cfg, batch, dtype)
    if kind == "decoder":
        return attn.init_kv_cache(cfg, batch, max_len, ATTN_GLOBAL, dtype)
    raise ValueError(kind)


def write_decode(cache, new, kind: str, pos, active=None):
    """Second half of a decode step for one block (or a scanned stack of
    blocks): ``new`` is what ``apply_block`` returned in decode mode.
    Attention blocks write their new K/V rows into ``cache`` in place;
    SSM / RG-LRU states are small, per-slot and replaced whole."""
    if kind in (ATTN_GLOBAL, ATTN_LOCAL, "decoder"):
        akind = ATTN_GLOBAL if kind == "decoder" else kind
        return attn.write_decode_rows(cache, new, pos, akind, active)
    return new


def _residual_mlp(p, x, cfg: ModelConfig, aux):
    h = apply_norm(p["pre_mlp_norm"], x, cfg.norm_type, cfg.norm_eps)
    if "moe" in p:
        y, a = moe_mod.moe_apply(p["moe"], h, cfg)
        aux = aux + a if aux is not None else None
    else:
        y = mlp_mod.apply_mlp(p["mlp"], h, cfg)
    if "post_mlp_norm" in p:
        y = apply_norm(p["post_mlp_norm"], y, cfg.norm_type, cfg.norm_eps)
    return x + y, aux


def apply_block(p, x, cfg: ModelConfig, kind: str, *, mode: str,
                positions=None, cache=None, pos=None, kv_valid=None,
                cross_kv=None, cross_valid=None, causal: bool = True,
                aux=None, active=None):
    """mode: 'full' (train/encode), 'prefill', 'chunk' (one prompt chunk
    against a live cache — ``pos`` carries per-row chunk coordinates
    ``(slots, start, write_pos, lengths)``), or 'decode' (``active``
    (B,) bool marks the rows really decoding; inactive rows' per-slot
    state is frozen so a dummy step cannot corrupt a mid-chunked-prefill
    row; attention blocks return their new K/V rows in place of a
    cache, for ``write_decode``). Every state-carrying kind chunks:
    global KV scatters at offsets, local rings write at ring offsets,
    SSM / RG-LRU carry the entering state + conv tail across the
    boundary."""
    h = apply_norm(p["pre_norm"], x, cfg.norm_type, cfg.norm_eps)
    new_cache = cache

    if mode == "chunk" and kind not in CHUNKABLE_KINDS:
        raise ValueError(
            f"chunked prefill cannot cross block kind {kind!r}: "
            f"cross-attention decoder state has no per-slot chunk "
            f"contract (see repro.serving.state.require_chunkable)")

    if kind in (ATTN_GLOBAL, ATTN_LOCAL, "decoder"):
        akind = ATTN_GLOBAL if kind == "decoder" else kind
        if mode == "decode":
            # the new token's K/V rows, not a whole cache: write_decode
            # writes them once the layer loop is done
            y, new_cache = attn.decode_attend(p["attn"], h, cache, pos, cfg,
                                              akind)
        elif mode == "chunk":
            y, new_cache = attn.chunk_prefill_attention(p["attn"], h, cache,
                                                        pos, cfg, akind)
        else:
            y, kv = attn.full_attention(p["attn"], h, cfg, akind, positions,
                                        kv_valid=kv_valid, causal=causal)
            if mode == "prefill":
                new_cache = attn.fill_cache_from_prefill(cache, kv[0], kv[1],
                                                         akind, cfg,
                                                         kv_valid=kv_valid)
    elif kind == SSM:
        if mode == "decode":
            y, new_cache = ssm_mod.ssm_decode_step(p["ssm"], h, cache, cfg,
                                                   active=active)
        elif mode == "chunk":
            y, new_cache = ssm_mod.ssm_chunk_step(p["ssm"], h, cache, cfg,
                                                  pos)
        else:
            y, new_cache = ssm_mod.ssm_forward(p["ssm"], h, cfg,
                                               return_state=(mode == "prefill"),
                                               valid=kv_valid)
        return x + y, new_cache, aux               # mamba: no MLP half
    elif kind == RECURRENT:
        if mode == "decode":
            y, new_cache = rglru_mod.rglru_decode_step(p["rec"], h, cache,
                                                       cfg, active=active)
        elif mode == "chunk":
            y, new_cache = rglru_mod.rglru_chunk_step(p["rec"], h, cache,
                                                      cfg, pos)
        else:
            y, new_cache = rglru_mod.rglru_forward(p["rec"], h, cfg,
                                                   return_state=(mode == "prefill"),
                                                   valid=kv_valid)
    else:
        raise ValueError(kind)

    if "post_norm" in p:
        y = apply_norm(p["post_norm"], y, cfg.norm_type, cfg.norm_eps)
    x = x + y

    if kind == "decoder":
        h = apply_norm(p["xattn_norm"], x, cfg.norm_type, cfg.norm_eps)
        y, _ = attn.full_attention(p["xattn"], h, cfg, ATTN_GLOBAL, None,
                                   kv_valid=cross_valid, cross_kv=cross_kv)
        x = x + y

    x, aux = _residual_mlp(p, x, cfg, aux)
    return x, new_cache, aux
