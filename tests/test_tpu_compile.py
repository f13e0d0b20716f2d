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
import re

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


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _compile(fn, one_chip, *shapes):
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


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


@pytest.mark.parametrize("layers", [None, 32], ids=["one_layer", "stacked"])
def test_paged_decode_compiles(one_chip, layers):
    # one layer's pool, or the serving pool stacked over all 32 layers and
    # read at a layer index, as the decode step's layer scan calls it
    slots, blocks, block, n_max = 8, 1024 if layers is None else 256, 128, 24
    pool = (blocks, block, KV_HEADS, HD) if layers is None else (
        layers, blocks, block, KV_HEADS, HD)

    def paged(q, pool_k, pool_v, tables, lengths, *layer):
        return kattn.paged_decode_attention(q, pool_k, pool_v, tables, lengths,
                                            *layer, interpret=False)

    _compile(paged, one_chip,
             ((slots, 1, HEADS, HD), BF16), (pool, BF16), (pool, BF16),
             ((slots, n_max), I32), ((slots,), I32),
             *([] if layers is None else [((), I32)]))


# LFM2-8B-A1B's attention layer: 32 query and 8 KV heads of 64, sequence 8192
LFM2_QKV = [((1, 8192, 32, 64), BF16), ((1, 8192, 8, 64), BF16), ((1, 8192, 8, 64), BF16)]


def test_flash_head_dim_64_compiles(one_chip):
    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(_flash(*a).astype(F32)),
                        argnums=(0, 1, 2))(q, k, v)

    _compile(grads, one_chip, *LFM2_QKV)


@pytest.mark.parametrize("k,n", [(2048, 1792), (1792, 2048)], ids=["up", "down"])
def test_grouped_matmul_compiles(one_chip, k, n):
    # an expert layer's projections over the sorted buffer of one microbatch
    # (2 x 8192 tokens x top-4 rows) and 8 held experts: forward, then the
    # transposed gmm and the tgmm of the backward
    from repro.kernels.gmm import grouped_matmul

    def grads(x, w, sizes):
        return jax.grad(lambda x, w: jnp.sum(
            grouped_matmul(x, w, sizes, interpret=False).astype(F32)), (0, 1))(x, w)

    _compile(grads, one_chip, ((65_536, k), BF16), ((8, k, n), BF16), ((8,), I32))


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


def test_head_backward_carries_no_f32_weight_gradient(one_chip):
    """The LM head's backward at Yi widths (vocab 64000, chunks of 512):
    the chunk loop keeps each chunk's bf16 dlogits and the weight gradient
    is one dot after it, so no loop carries an f32 accumulator of the
    head's shape through a read-modify-write at every chunk."""
    from repro.models import transformer as tf

    def grads(x, kernel, targets):
        return jax.grad(lambda a, k: tf.xent_chunked(a, k, targets, 512, None),
                        argnums=(0, 1))(x, kernel)

    text = _compiled_text(grads, one_chip, ((1, SEQ, D_MODEL), BF16),
                          ((D_MODEL, 64_000), F32), ((1, SEQ), I32))
    carries = [line.split(" while(")[0] for line in text.splitlines()
               if re.search(r" while\(", line)]
    assert carries, "the chunk loop is gone"
    assert not any(f"f32[{D_MODEL},64000]" in c for c in carries), carries


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_paged_programs_update_the_pool_in_place(one_chip, program, monkeypatch):
    """The donated paged programs on the Pallas lane, compiled for the chip
    at the serving pool's tile shapes (blocks of 128 rows of 4 KV heads of
    128): the pool-shaped instructions are the in-place scatters and
    nothing else.  The chip's compiler is the one that lays a pool out anew
    around a scatter of whole blocks, which a CPU compile does not show."""
    import repro.kernels
    from _hlo import pool_ops
    from repro.configs.base import ModelConfig
    from repro.models import transformer as tf

    # the model leaves interpret mode to the lane, which sees the CPU here
    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)
    cfg = ModelConfig(
        name="t", family="dense", num_layers=2, d_model=512, num_heads=4,
        num_kv_heads=KV_HEADS, head_dim=HD, d_ff=1024, vocab_size=512,
        pattern=("attn",), param_dtype="bfloat16", compute_dtype="bfloat16",
        remat=False, attn_impl="pallas",
    )

    def spec(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = spec(jax.eval_shape(lambda: tf.init_params(cfg, jax.random.key(0))))
    pages = spec(jax.eval_shape(lambda: tf.init_pages(cfg, 64, 128)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)  # noqa: E731
    if program == "decode_step":
        def fn(params, cache, pages, tables, toks):
            return tf.decode_step(cfg, params, cache, toks, pages=pages,
                                  tables=tables)

        args = (params, {"len": i32(8)}, pages, i32(8, 16), i32(8, 1))
    else:
        def fn(params, pages, row, toks, ptab, wtab, off):
            return tf.prefill_chunk(cfg, params, row, pages,
                                    {"tokens": toks}, off, ptab, wtab)

        args = (params, pages, {"len": i32(1)}, i32(1, 256), i32(2), i32(2),
                i32())
    text = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    shape = "bf16[" + ",".join(map(str, pages["pos0"]["k"].shape)) + "]"
    ops = pool_ops(text, shape)
    assert {op for _, op in ops} <= {"parameter", "get-tuple-element", "scatter"}, ops
    assert sum(op == "scatter" for _, op in ops) >= 2, ops
