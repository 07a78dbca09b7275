"""QuantizedParams build step (paper §V): per-channel int8 weights + scales
for every dense projection in the LM stack, driven by the calibration +
skip-list workflow in ``core/quantization.py``.

Every MLP projection (``w_gate``/``w_up``/``w_down``) and attention
projection (``wq``/``wk``/``wv``/``wo``) is a quantization SITE, named
``scan{i}.{module}.{weight}`` / ``tail{i}.{module}.{weight}``. A site in
the scan unit covers all ``repeats`` stacked copies at that position (the
decision is per-site, the quantization vmapped over the leading repeats
dim — the quantized leaves slice through ``jax.lax.scan`` exactly like
the fp32 originals). Embeddings, norms, the LM head, MoE experts,
SSM/RG-LRU mixers, and enc-dec cross-attention stay fp32 (the skip-list:
``build_cross_kv`` and the mixers touch their weights directly).

The workflow quantizes every site, measures end-to-end top-1 token
disagreement vs the fp32 reference on a deterministic calibration batch,
and while the disagreement exceeds ``budget`` falls the highest-error
site back to fp32 — the paper's "increase precision for operators that
incur high quantization errors" loop.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.quantization import (QuantWorkflowResult,
                                     quantization_workflow,
                                     quantize_weight_int8)

# module -> weight names that are dense GEMM sites
QUANT_SITES = {"mlp": ("w_gate", "w_up", "w_down"),
               "attn": ("wq", "wk", "wv", "wo")}


@dataclass
class QuantizedParams:
    """Result of the build step: ``params`` is the original pytree with
    int8-decided sites replaced by ``{"q8", "scale"}`` leaves."""
    params: Dict[str, Any]
    result: QuantWorkflowResult
    quantized_sites: int
    fallback_sites: int

    @property
    def schemes(self) -> Dict[str, str]:
        return {d.name: d.scheme for d in self.result.decisions}


def _collect_sites(params) -> Dict[str, Tuple[str, int, str, str]]:
    """site name -> ('scan'|'tail', position, module, weight)."""
    sites = {}
    for group in ("scan", "tail"):
        for gi, blockp in enumerate(params.get(group, ())):
            for mod, wnames in QUANT_SITES.items():
                if mod not in blockp:
                    continue
                for wname in wnames:
                    if wname in blockp[mod]:
                        sites[f"{group}{gi}.{mod}.{wname}"] = \
                            (group, gi, mod, wname)
    return sites


def _as_2d(w: jax.Array, wname: str) -> jax.Array:
    """Flatten a dense weight to (reduction, output). ``wo`` (H, hd, d)
    contracts its leading head axes; every other site ((d, H, hd) head
    projections, 2-D MLP weights) contracts its leading axis — head axes
    flatten into the output axis and ``models/attention.py`` restores
    them from ``cfg.head_dim`` at apply time."""
    if wname == "wo":
        return w.reshape(-1, w.shape[-1])
    return w.reshape(w.shape[0], -1)


def _quantize_leaf(w: jax.Array, wname: str) -> Dict[str, jax.Array]:
    q, s = quantize_weight_int8(_as_2d(w, wname))
    return {"q8": q, "scale": s}


def _quantize_site(w: jax.Array, wname: str,
                   stacked: bool) -> Dict[str, jax.Array]:
    if stacked:            # (repeats, in, ...) — quantize each copy
        return jax.vmap(lambda w: _quantize_leaf(w, wname))(w)
    return _quantize_leaf(w, wname)


def _site_error(w: jax.Array, wname: str, stacked: bool) -> float:
    """Relative dequant error of the site (max over stacked repeats)."""
    def one(w):
        w2 = _as_2d(w, wname).astype(jnp.float32)
        q, s = quantize_weight_int8(w2)
        deq = q.astype(jnp.float32) * s
        num = jnp.linalg.norm(w2 - deq)
        den = jnp.maximum(jnp.linalg.norm(w2), 1e-8)
        return num / den
    errs = jax.vmap(one)(w) if stacked else one(w)
    return float(jnp.max(errs))


def materialize(params, schemes: Dict[str, str],
                quantized_leaves: Dict[str, Any]):
    """Rebuild the params pytree with int8-decided sites swapped for their
    precomputed quantized leaves (fp16-decided sites keep the original)."""
    sites = _collect_sites(params)
    new = dict(params)
    for group in ("scan", "tail"):
        if group not in new:
            continue
        blocks = [dict(b) for b in new[group]]
        for name, scheme in schemes.items():
            if scheme != "int8" or name not in sites:
                continue
            g, gi, mod, wname = sites[name]
            if g != group:
                continue
            modp = dict(blocks[gi][mod])
            modp[wname] = quantized_leaves[name]
            blocks[gi][mod] = modp
        new[group] = tuple(blocks)
    return new


def default_calib_tokens(cfg: ModelConfig, batch: int = 2, seq: int = 16):
    """Deterministic calibration batch (the tests replay the same)."""
    return jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0,
                              cfg.vocab_size, dtype=jnp.int32)


def _full_argmax(params, cfg: ModelConfig, tokens):
    from repro.models import model as model_mod
    h, _, _ = model_mod.forward(params, cfg, {"tokens": tokens}, mode="full")
    table = model_mod.head_table(params, cfg)
    logits = jnp.einsum("bsd,vd->bsv", h.astype(jnp.float32),
                        table.astype(jnp.float32))
    return jnp.argmax(logits[..., :cfg.vocab_size], axis=-1)


def build_quantized_params(cfg: ModelConfig, params, *,
                           budget: float = 0.05,
                           calib_tokens: Optional[jax.Array] = None,
                           skip: Tuple[str, ...] = (),
                           max_iters: int = 4) -> QuantizedParams:
    """Run the §V workflow over every dense projection site and return the
    mixed-precision params. ``budget`` bounds the top-1 token disagreement
    vs the fp32 reference on the calibration batch; ``skip`` force-keeps
    named sites (substring match) fp32."""
    if calib_tokens is None:
        calib_tokens = default_calib_tokens(cfg)
    sites = _collect_sites(params)
    sites = {n: loc for n, loc in sites.items()
             if not any(s in n for s in skip)}

    def leaf_of(name):
        group, gi, mod, wname = sites[name]
        return params[group][gi][mod][wname]

    # quantize every site once up front; workflow iterations just re-mix
    quantized = {n: _quantize_site(leaf_of(n), sites[n][3],
                                   sites[n][0] == "scan")
                 for n in sites}
    ref_argmax = _full_argmax(params, cfg, calib_tokens)

    def eval_metric(schemes: Dict[str, str]) -> float:
        qp = materialize(params, schemes, quantized)
        qa = _full_argmax(qp, cfg, calib_tokens)
        return float(jnp.mean((qa != ref_argmax).astype(jnp.float32)))

    def site_error(name, _w):
        return _site_error(leaf_of(name), sites[name][3],
                           sites[name][0] == "scan")

    result = quantization_workflow(
        {n: leaf_of(n) for n in sites}, eval_metric, budget=budget,
        layer_error_fn=site_error, max_iters=max_iters)
    final = materialize(params, {d.name: d.scheme for d in result.decisions},
                        quantized)
    n_int8 = sum(d.scheme == "int8" for d in result.decisions)
    return QuantizedParams(final, result, n_int8,
                           len(result.decisions) - n_int8)
