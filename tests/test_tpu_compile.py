"""Compile the main path's kernels and the engine's decode and chunk
steps for a described TPU v5e, at deepseek-7b widths, with no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks that break
the (8, 128) tiling, too much VMEM, programs that do not fit HBM. These
tests catch such refusals on the CPU. Nothing here runs a program.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a test worker that
decided at import whether these tests exist would collect a different
test set from its siblings.
"""
import dataclasses
import functools
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import model as model_mod
from repro.serving.engine import InferenceEngine

V5E_HBM = 16e9
D_MODEL, D_FF, HEADS, HEAD_DIM = 4096, 11008, 32, 128   # deepseek-7b


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without the chip: keep the cache out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("M", [8, 512])           # decode / prefill rows
def test_w8a8_matmul_compiles_for_v5e(one_chip, M):
    from repro.kernels.w8a8.matmul import w8a8_matmul
    args = (_spec(one_chip, (M, D_MODEL), jnp.int8),
            _spec(one_chip, (D_MODEL, D_FF), jnp.int8),
            _spec(one_chip, (M,), jnp.float32),
            _spec(one_chip, (D_FF,), jnp.float32))
    fn = functools.partial(w8a8_matmul, interpret=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_heads", [HEADS, 1])   # MHA / MQA
def test_flash_attn_compiles_for_v5e(one_chip, kv_heads):
    from repro.kernels.flash_attn.ops import flash_attn
    B, S = 1, 512
    q = _spec(one_chip, (B, S, HEADS, HEAD_DIM), jnp.bfloat16)
    kv = _spec(one_chip, (B, S, kv_heads, HEAD_DIM), jnp.bfloat16)
    fn = functools.partial(flash_attn, interpret=False)
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_engine_step(sharding, cfg, stage, slots=8, max_len=1024,
                         chunk=128):
    """The engine's decode or chunk-prefill executable for ``slots`` x
    ``max_len`` positions, compiled for the described chip; returns
    (compiled, abstract caches)."""
    place = lambda tree: jax.tree.map(
        lambda a: _spec(sharding, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(functools.partial(model_mod.init_params,
                                                    cfg),
                                  jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(
        lambda: model_mod.init_caches(cfg, slots, max_len)))
    rows = lambda *shape: _spec(sharding, (slots,) + shape, jnp.int32)
    # _build_decode and _build_chunk read only the config off the engine
    eng = types.SimpleNamespace(cfg=cfg)
    if stage == "decode":
        step = InferenceEngine._build_decode(eng)
        args = (rows(1), rows(), _spec(sharding, (slots,), jnp.bool_))
    else:
        step = InferenceEngine._build_chunk(eng, chunk)
        # slots, tokens, start, write_pos, lengths, last_idx
        args = (rows(), rows(chunk), rows(), rows(), rows(), rows())
    return step.lower(params, caches, *args).compile(), caches


@pytest.mark.parametrize("stage", ["decode", "chunk"])
def test_engine_step_compiles_for_v5e(one_chip, stage):
    """The engine's decode step and its 128-token chunk-prefill step for
    8 slots x 1024 positions, deepseek-7b widths at 2 layers."""
    cfg = dataclasses.replace(get_config("deepseek-7b"), num_layers=2)
    compiled, _ = _compile_engine_step(one_chip, cfg, stage)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM


def _entry_computation(hlo_text):
    start = hlo_text.index("\nENTRY")
    return hlo_text[start:hlo_text.index("\n}\n", start)]


# deepseek-7b: global MHA, two scanned layers. gemma2-27b: (local, global)
# superblocks, four layers so the unit is scanned twice and the ring
# layers go through the loop too (one repeat compiles with no loop).
@pytest.mark.parametrize("arch,layers", [("deepseek-7b", 2),
                                         ("gemma2-27b", 4)])
def test_decode_step_writes_only_new_rows(one_chip, arch, layers):
    """The decode step carries the K/V caches through the layer loop
    read-only and writes each layer's new row after it, in place: its
    temporaries stay below one layer's K cache (a loop that carried
    whole caches as outputs held a second copy of them, 403 MB here for
    deepseek-7b), and the entry computation copies no cache-shaped
    buffer."""
    cfg = dataclasses.replace(get_config(arch), num_layers=layers)
    compiled, caches = _compile_engine_step(one_chip, cfg, "decode")
    layer_k = caches["scan"][0]["k"]
    one_layer = layer_k.size // layer_k.shape[0] * layer_k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer
    dims = set()
    for leaf in jax.tree.leaves(caches):
        dims.update({",".join(map(str, leaf.shape)),
                     ",".join(map(str, leaf.shape[1:]))})
    copies = re.findall(r"= \w+\[([\d,]*)\]\S* copy\(",
                        _entry_computation(compiled.as_text()))
    assert not dims & set(copies), copies


def test_dlrm_sparse_stage_compiles_for_v5e(one_chip):
    """DLRM-DCNv2's sparse stage at published widths on one chip's half of
    its tables, for 160 items: the staged (B, W = 512) ids, as the rank
    traffic ships them, need under a twentieth of the temporaries that
    gathering the padded (B, 26, 100) bags does (2 MB against 114 MB
    here)."""
    from repro.configs.dlrm_dcnv2 import CONFIG
    from repro.models import dlrm as D
    rows = tuple(r // 2 if r >= 1_000_000 else r for r in CONFIG.table_rows)
    cfg = dataclasses.replace(CONFIG, table_rows=rows)
    asn = D.make_assignment(cfg, 1)
    R, B, T = asn.total_rows, 160, cfg.num_tables
    slab = {"q8": _spec(one_chip, (R, cfg.embed_dim), jnp.int8),
            "scale": _spec(one_chip, (R,), jnp.float16),
            "bias": _spec(one_chip, (R,), jnp.float16),
            "sb": _spec(one_chip, (-(-R // D.SB_ROWS), 128), jnp.float16)}
    fn = jax.jit(lambda p, i, l: D.sls_forward(p, cfg, asn, i, l))
    temp = {}
    for ids in [(B, 512), (B, T, cfg.max_lookups_per_table)]:
        compiled = fn.lower({"slab_q": slab}, _spec(one_chip, ids, jnp.int32),
                            _spec(one_chip, (B, T), jnp.int32)).compile()
        temp[len(ids)] = compiled.memory_analysis().temp_size_in_bytes
    assert 20 * temp[2] < temp[3], temp
