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
