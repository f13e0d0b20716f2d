"""Every kernel of the Pallas lane compiles for a TPU v5e chip at Yi-6B widths.

Interpret mode (tests/test_kernels.py) checks what the kernels compute; it
cannot see what the chip's compiler refuses — block shapes not aligned to
the (8, 128) tiling, layouts the lowering does not accept, more VMEM than a
kernel may take.  These tests compile each kernel for a *described* v5e
chip (no device needed, only the TPU compiler) at the shapes the main path
runs: head_dim 128, 32 query and 4 KV heads, sequence 2048, d_model 4096,
d_ff 11008.  Each compiled program must contain the Mosaic kernel
(``tpu_custom_call``), so nothing fell back to XLA.  This process sees the
CPU backend, where the lane defaults to interpret mode, so every call here
passes ``interpret=False``.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, so describing it while pytest-xdist
workers import this file would break the other workers.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import attention as kattn
from repro.kernels import ops, psgn, quant

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
SEQ, HEADS, KV_HEADS, HD = 2048, 32, 4, 128
D_MODEL, D_FF = 4096, 11_008


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


QKV = [((1, SEQ, HEADS, HD), BF16), ((1, SEQ, KV_HEADS, HD), BF16),
       ((1, SEQ, KV_HEADS, HD), BF16)]


def _flash(q, k, v):
    # the transformer's train/prefill call: causal, q_block 512, kv_block 1024
    return kattn.flash_attention(q, k, v, True, None, None, 512, 1024, False)


def test_flash_forward_compiles(one_chip):
    _compile(_flash, one_chip, *QKV)


def test_flash_backward_compiles(one_chip):
    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(_flash(*a).astype(F32)),
                        argnums=(0, 1, 2))(q, k, v)

    _compile(grads, one_chip, *QKV)


def test_chunk_attention_compiles(one_chip):
    # a 256-token prefill chunk over 1024 prior positions
    c, sk = 256, 1024 + 256

    def chunk(q, k, v, q_pos, k_pos, k_valid):
        return kattn.chunk_attention(q, k, v, q_pos, k_pos, k_valid,
                                     q_block=c, kv_block=1024, interpret=False)

    _compile(chunk, one_chip, ((1, c, HEADS, HD), BF16),
             ((1, sk, KV_HEADS, HD), BF16), ((1, sk, KV_HEADS, HD), BF16),
             ((c,), I32), ((sk,), I32), ((sk,), jnp.bool_))


def test_paged_decode_compiles(one_chip):
    slots, blocks, block, n_max = 8, 1024, 128, 24

    def paged(q, pool_k, pool_v, tables, lengths):
        return kattn.paged_decode_attention(q, pool_k, pool_v, tables, lengths,
                                            interpret=False)

    _compile(paged, one_chip,
             ((slots, 1, HEADS, HD), BF16),
             ((blocks, block, KV_HEADS, HD), BF16),
             ((blocks, block, KV_HEADS, HD), BF16),
             ((slots, n_max), I32), ((slots,), I32))


FFN_ACTS = [((1, SEQ, D_MODEL), BF16), ((1, SEQ, D_FF), BF16)]


def test_psgn_direct_compiles(one_chip):
    _compile(lambda x, d: ops.persample_sq_norm(x, d, method="direct",
                                                interpret=False),
             one_chip, *FFN_ACTS)


def test_psgn_gram_compiles(one_chip):
    # the dispatch's block choice has to keep the streamed blocks in VMEM
    _compile(lambda x, d: ops.persample_sq_norm(x, d, method="gram",
                                                interpret=False),
             one_chip, *FFN_ACTS)


def test_psgn_fused_compiles(one_chip):
    # the four attention projections of one layer, stacked (q and o shapes)
    _compile(lambda x, d: psgn.psgn_fused(x, d, interpret=False), one_chip,
             ((2, 1, SEQ, D_MODEL), BF16),
             ((2, 1, SEQ, D_MODEL), BF16))


def test_quantize_int8_compiles(one_chip):
    _compile(lambda x: quant.quantize_int8(x, interpret=False), one_chip,
             ((D_MODEL, D_FF), F32))
