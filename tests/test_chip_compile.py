"""The flash-attention kernels compile for a real chip (no chip attached).

The TPU compiler is installed in the CPU sandbox and compiles for a DESCRIBED
`v5e:2x2` topology, which shows what interpret mode cannot: Mosaic tiling
and VMEM limits at bert_long_sp's attention widths. Nothing runs — a compile
that passes is not a chip run (`chip_smoke.py` is).

The topology is described inside a module-scoped fixture (only one process
at a time may load libtpu, and every xdist worker imports this file), and
all four compiles live in this one file so one worker owns the library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dnn_page_vectors_tpu.ops.flash_attention import flash_attention

# bert_long_sp attention widths (config.py:bert_long_sp), batch 8
B, H, L, DH = 8, 8, 1024, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile can be written to the persistent cache but
    # never read back without the chip: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("mode,bias,seg", [
    ("forward", False, False),
    ("grad", False, False),
    ("grad", True, False),
    ("grad", False, True),
], ids=["forward", "grad", "grad_bias", "grad_seg"])
def test_flash_compiles_for_v5e(one_chip, mode, bias, seg):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    qkv = [shape((B, H, L, DH), jnp.bfloat16)] * 3
    args = (*qkv, shape((B, L), jnp.bool_),
            shape((H, L, L), jnp.float32) if bias else None,
            shape((B, L), jnp.int32) if seg else None)

    def attn(q, k, v, kv_mask, bias, seg):
        return flash_attention(q, k, v, kv_mask, bias=bias, seg=seg,
                               interpret=False)

    fn = attn if mode == "forward" else jax.grad(
        lambda *a: jnp.sum(attn(*a)), (0, 1, 2, 4) if bias else (0, 1, 2))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# GLM-4.7-Flash's widths (config.py:glm47_flash_ep8): one group of 4 rows
GLM_B, GLM_H, GLM_L, GLM_DH = 4, 20, 1024, 256


@pytest.mark.parametrize("mode", ["forward", "grad"])
def test_causal_flash_compiles_for_v5e_at_head_width_256(one_chip, mode):
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                     sharding=one_chip)
    qkv = [shape((GLM_B, GLM_H, GLM_L, GLM_DH), jnp.bfloat16)] * 3
    mask = shape((GLM_B, GLM_L), jnp.bool_)

    def attn(q, k, v, kv_mask):
        return flash_attention(q, k, v, kv_mask, block_q=512, block_kv=512,
                               causal=True, interpret=False)

    fn = attn if mode == "forward" else jax.grad(
        lambda *a: jnp.sum(attn(*a).astype(jnp.float32)), (0, 1, 2))
    text = jax.jit(fn).lower(*qkv, mask).compile().as_text()
    assert text.count("tpu_custom_call") >= (1 if mode == "forward" else 3)


@pytest.mark.parametrize("dim,batch", [(256, 8), (1024, 8), (256, 200)])
def test_exact_scan_compiles_for_v5e_at_the_store_widths(one_chip, dim,
                                                         batch):
    """`exact_scan` over a 65,536-row shard of pair words at the benchmark's
    two widths (bert_mini's 256, the hybrid towers' 1,024) and a query
    batch past one query block: Mosaic takes the integer decode, the
    running top-k's loop and its VMEM, and the words go to the kernel with
    no XLA pass over them."""
    import functools

    from dnn_page_vectors_tpu.ops.topk import _kernel_topk
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,  # noqa: E731
                                                     sharding=one_chip)
    text = jax.jit(functools.partial(_kernel_topk, k=10, interpret=False)
                   ).lower(shape((batch, dim), jnp.float32),
                           shape((65536, dim // 2), jnp.uint32),
                           valid=shape((), jnp.int32)).compile().as_text()
    entry = text[text.index("ENTRY"):]
    # the kernel reads the words as they were put, no XLA op between
    assert re.search(r"custom-call\([^)]*%pages\.\d+\)", entry)
    assert "tpu_custom_call" in entry


# a buffer of 72 tiles (the worst case) and one of 24 (the expected load)
@pytest.mark.parametrize("mode,n_tiles", [
    ("forward", None), ("grad", None), ("forward", 24), ("grad", 24)])
def test_grouped_matmul_compiles_for_v5e_at_the_expert_widths(one_chip, mode,
                                                              n_tiles):
    from dnn_page_vectors_tpu.ops import grouped_matmul as gm
    tokens, top_k, held, tile, d, ff = 4096, 4, 8, 256, 2048, 1536
    assert gm.expected_tiles(tokens, top_k, held, 64, tile) == 24
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                     sharding=one_chip)

    def experts(x, chosen, w_up, w_down, weight):
        plan = gm.plan_rows(chosen, 0, held, tile, n_tiles)
        h = gm.grouped_matmul(gm.permute(x, plan), w_up, plan, tile,
                              interpret=False)
        rows = gm.grouped_matmul(jax.nn.silu(h), w_down, plan, tile,
                                 interpret=False)
        return gm.unpermute(rows, weight, plan)

    fn = experts if mode == "forward" else jax.grad(
        lambda *a: jnp.sum(experts(*a)), (0, 2, 3, 4))
    text = jax.jit(fn).lower(
        shape((tokens, d), jnp.bfloat16), shape((tokens, top_k), jnp.int32),
        shape((held, d, ff), jnp.bfloat16),
        shape((held, ff, d), jnp.bfloat16),
        shape((tokens, top_k), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") >= (2 if mode == "forward" else 6)


def test_routed_layer_compiles_for_v5e_with_both_buffer_sizes(one_chip,
                                                              monkeypatch):
    """The layer's value-and-grad at the cell's widths: one program holding
    the expected-load path and the worst-case fallback, the kernels compiled
    by Mosaic at both sizes (forward 3 + backward 3 forward again and 6
    more, for each size)."""
    import functools
    from dnn_page_vectors_tpu.models import glm_moe
    from dnn_page_vectors_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, interpret=False))
    layer = glm_moe.RoutedExperts(2048, 1536, 64, 4, 1.8, 8, 0,
                                  dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((GLM_B, GLM_L, 2048), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x))
    text = jax.jit(jax.value_and_grad(
        lambda p, v: jnp.sum(layer.apply(p, v)[0].astype(jnp.float32)),
        (0, 1))).lower(params, x).compile().as_text()
    assert text.count(" conditional(") >= 2             # forward, backward
    assert text.count("tpu_custom_call") >= 2 * (3 + 3 + 6)


# Granite-4.0-H-Small's widths (config.py:granite4_h_small_ep2): queries of
# 1,024 tokens in buckets of 1 and 4, weights held in bfloat16
@pytest.mark.parametrize("kind,bucket", [("mamba", 1), ("mamba", 4),
                                         ("attention", 4)])
def test_hybrid_block_compiles_for_v5e_at_the_published_widths(
        one_chip, monkeypatch, kind, bucket):
    """One layer's forward as the serve path runs it: the grouped product
    at hidden 4,096 / width 768 / 36 held / 10 a token (one path, no
    `cond`: the expected-load buffer is the worst case's), the causal flash
    kernel under 32 query and 8 key/value heads of 128, and the chunked
    scan's products, within the chip's memory."""
    import functools
    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.infer.bulk_embed import hold_weights
    from dnn_page_vectors_tpu.models import granite_hybrid
    from dnn_page_vectors_tpu.models.factory import build_two_tower
    from dnn_page_vectors_tpu.ops import flash_attention as fa
    from dnn_page_vectors_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, interpret=False))
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=False))
    sizes = build_two_tower(get_config("granite4_h_small_ep2"),
                            50_176).query_tower.sizes
    block = granite_hybrid.HybridBlock(sizes, kind, dtype=jnp.bfloat16)
    h = jax.ShapeDtypeStruct((bucket, 1024, 4096), jnp.bfloat16,
                             sharding=one_chip)
    mask = jax.ShapeDtypeStruct((bucket, 1024), jnp.bool_, sharding=one_chip)
    held = jax.eval_shape(
        lambda t: hold_weights(t, "bfloat16"),
        jax.eval_shape(block.init, jax.random.PRNGKey(0), h, mask))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        held)
    compiled = jax.jit(lambda p, x, m: block.apply(p, x, m)[0]).lower(
        params, h, mask).compile()
    text = compiled.as_text()
    assert " conditional(" not in text
    assert text.count("tpu_custom_call") == (4 if kind == "attention" else 3)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


# Falcon-H1-34B's widths (config.py:falcon_h1_34b_pp12): one query of 1,024
# tokens a call, weights held in bfloat16
def test_falcon_h1_block_compiles_for_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """One layer's forward as the serve path runs it: the chunked scan's
    products with 2 groups and a 128 x 256 state a head (chunk 128), the
    causal flash kernel under 20 query and 4 key/value heads of 128 after
    rotary, and the 21,504-wide SwiGLU, within the chip's memory."""
    import functools
    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.infer.bulk_embed import hold_weights
    from dnn_page_vectors_tpu.models import falcon_h1
    from dnn_page_vectors_tpu.models.factory import build_two_tower
    from dnn_page_vectors_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=False))
    sizes = build_two_tower(get_config("falcon_h1_34b_pp12"),
                            261_120).query_tower.sizes
    block = falcon_h1.FalconH1Block(sizes, dtype=jnp.bfloat16)
    h = jax.ShapeDtypeStruct((1, 1024, 5120), jnp.bfloat16,
                             sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 1024), jnp.bool_, sharding=one_chip)
    held = jax.eval_shape(
        lambda t: hold_weights(t, "bfloat16"),
        jax.eval_shape(block.init, jax.random.PRNGKey(0), h, mask))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        held)
    compiled = jax.jit(block.apply).lower(params, h, mask).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1           # flash_fwd
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
