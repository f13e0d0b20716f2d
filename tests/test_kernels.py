"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode on CPU; TPU is the execution target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import default_interpret, ops, ref
from repro.kernels.psgn import psgn_direct, psgn_gram
from repro.kernels.quant import dequantize_int8, quantize_int8

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


PSGN_SHAPES = [
    (2, 64, 32, 48),
    (3, 128, 16, 96),
    (1, 37, 19, 23),   # ragged: exercises padding
    (2, 256, 128, 128),
    (4, 33, 7, 130),
]


@pytest.mark.parametrize("shape", PSGN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_psgn_direct_matches_ref(shape, dtype):
    b, s, di, do = shape
    x, d = _rand((b, s, di), dtype), _rand((b, s, do), dtype)
    got = psgn_direct(x, d, block_i=16, block_j=16, block_s=32)
    want = ref.psgn_ref(x, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


@pytest.mark.parametrize("shape", PSGN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_psgn_gram_matches_ref(shape, dtype):
    b, s, di, do = shape
    x, d = _rand((b, s, di), dtype), _rand((b, s, do), dtype)
    got = psgn_gram(x, d, block_si=32, block_sj=32)
    want = ref.psgn_ref(x, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


def test_gram_identity_refs_agree():
    x, d = _rand((2, 50, 12), jnp.float32), _rand((2, 50, 20), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ref.psgn_ref(x, d)), np.asarray(ref.psgn_gram_ref(x, d)), rtol=1e-5
    )


def test_ops_auto_dispatch():
    # gram wins when S tiny vs features; direct when S large vs features
    assert ops.choose_method(s=16, d_in=4096, d_out=4096) == "gram"
    assert ops.choose_method(s=4096, d_in=64, d_out=64) == "direct"


def test_ops_2d_fast_path():
    x, d = _rand((5, 33), jnp.float32), _rand((5, 7), jnp.float32)
    got = ops.persample_sq_norm(x, d)
    want = ref.psgn_ref(x[:, None, :], d[:, None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_psgn_equals_vmap_grad_on_real_layer():
    """End-to-end: kernel psgn == per-sample grad norms of an actual dense
    layer computed by vmap(grad) — over a sequence model."""
    b, s, di, do = 3, 24, 10, 8
    w = _rand((di, do), jnp.float32)
    x = _rand((b, s, di), jnp.float32)
    y_target = _rand((b, s, do), jnp.float32)

    def loss_one(w, xb, yb):
        return 0.5 * jnp.sum((xb @ w - yb) ** 2)

    grads = jax.vmap(jax.grad(loss_one), in_axes=(None, 0, 0))(w, x, y_target)
    want = jnp.sum(grads.reshape(b, -1) ** 2, axis=-1)
    delta = x @ w - y_target  # dLoss/d(out)
    got = ops.persample_sq_norm(x, delta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4)


@pytest.mark.parametrize("shape", [(10, 64), (100, 257), (1, 7), (33, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_matches_ref(shape, dtype):
    x = _rand(shape, dtype)
    q, s = quantize_int8(x, block_rows=32)
    qr, sr = ref.quantize_int8_ref(x)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    # rounding ties at exact .5 boundaries may fall either way between the
    # fused kernel and the oracle (bf16 inputs hit them often) — allow off-
    # by-one on a tiny fraction of entries, never more.
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert diff.max() <= 1
    assert (diff > 0).mean() < 0.02


@pytest.mark.parametrize("case", range(12))
def test_psgn_property_random_shapes(case):
    """Property check: both Pallas factorisations agree with the pure-JAX
    reference on randomly drawn (B, S, Din, Dout) x dtype — the diversity
    numerator the batch controller consumes is kernel-verified, not just
    spot-checked on hand-picked shapes."""
    r = np.random.default_rng(1000 + case)
    b = int(r.integers(1, 5))
    s = int(r.integers(1, 97))
    di = int(r.integers(1, 90))
    do = int(r.integers(1, 90))
    dtype = (jnp.float32, jnp.bfloat16)[case % 2]
    x = jnp.asarray(r.standard_normal((b, s, di)), dtype)
    d = jnp.asarray(r.standard_normal((b, s, do)), dtype)
    want = np.asarray(ref.psgn_ref(x, d))
    direct = np.asarray(psgn_direct(x, d, block_i=16, block_j=16, block_s=32))
    gram = np.asarray(psgn_gram(x, d, block_si=32, block_sj=32))
    np.testing.assert_allclose(direct, want, rtol=3e-5, atol=1e-5)
    np.testing.assert_allclose(gram, want, rtol=3e-5, atol=1e-5)


def test_quantize_error_bound():
    x = _rand((50, 100), jnp.float32) * 10
    q, s = quantize_int8(x)
    deq = dequantize_int8(q, s)
    # max error <= scale/2 per row
    err = np.abs(np.asarray(deq - x))
    bound = np.asarray(s)[:, None] / 2 + 1e-6
    assert (err <= bound).all()


# ---------------------------------------------------------------------------
# attention kernels (PR 7): flash / chunk / paged-decode vs the jnp oracles
# ---------------------------------------------------------------------------

from _hypothesis_compat import given, settings, strategies as st  # noqa: E402

from repro.kernels import attention as kattn  # noqa: E402
from repro.models import attention as attn_lib  # noqa: E402
from repro.serve.blocks import BlockPool  # noqa: E402


def _qkv(r, b, sq, sk, h, kv, hd, dtype=jnp.float32):
    q = jnp.asarray(r.standard_normal((b, sq, h, hd)), dtype)
    k = jnp.asarray(r.standard_normal((b, sk, kv, hd)), dtype)
    v = jnp.asarray(r.standard_normal((b, sk, kv, hd)), dtype)
    return q, k, v


@settings(max_examples=14, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    sq=st.integers(min_value=1, max_value=70),
    window=st.sampled_from([None, 1, 5, 16]),
    softcap=st.sampled_from([None, 12.0]),
    causal=st.sampled_from([True, False]),
)
def test_flash_kernel_property(seed, sq, window, softcap, causal):
    """Pallas flash forward == the dense oracle across ragged lengths,
    sliding windows, softcap, and GQA (interpret mode)."""
    if not causal and window is not None:
        window = None  # the lane never windows non-causal attention
    r = np.random.default_rng(seed)
    sk = sq if causal else int(r.integers(1, 70))
    q, k, v = _qkv(r, 2, sq, sk, 4, 2, 16)
    got = kattn.flash_attention(q, k, v, causal, window, softcap, 16, 16, True)
    want = ref.flash_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    window=st.sampled_from([None, 7]),
    softcap=st.sampled_from([None, 20.0]),
)
def test_flash_kernel_backward_property(seed, window, softcap):
    """custom_vjp recompute backward == jax.grad through the oracle — the
    train path can adopt the kernel without changing gradients."""
    r = np.random.default_rng(seed)
    sq = int(r.integers(2, 40))
    q, k, v = _qkv(r, 2, sq, sq, 4, 2, 8)

    def loss_k(q, k, v):
        o = kattn.flash_attention(q, k, v, True, window, softcap, 16, 16, True)
        return jnp.sum(jnp.sin(o))

    def loss_r(q, k, v):
        o = ref.flash_ref(q, k, v, causal=True, window=window, softcap=softcap)
        return jnp.sum(jnp.sin(o))

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        assert not np.any(np.isnan(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    window=st.sampled_from([None, 6]),
    softcap=st.sampled_from([None, 15.0]),
)
def test_chunk_kernel_property(seed, window, softcap):
    """Serving chunk attention: explicit absolute positions + garbage key
    rows (k_valid=False), exactly the gathered-pool / windowed-ring layout."""
    r = np.random.default_rng(seed)
    c = int(r.integers(1, 24))
    prior = int(r.integers(0, 40))
    off = int(r.integers(0, 30))
    sk = prior + c
    q, k, v = _qkv(r, 1, c, sk, 4, 2, 16)
    q_pos = off + jnp.arange(c)
    k_pos = jnp.concatenate([jnp.arange(prior), q_pos]).astype(jnp.int32)
    k_valid = jnp.concatenate(
        [jnp.arange(prior) < off, jnp.ones((c,), bool)]
    )
    got = kattn.chunk_attention(q, k, v, q_pos, k_pos, k_valid, window=window,
                                softcap=softcap, q_block=8, kv_block=8,
                                interpret=True)
    want = ref.attention_ref(q, k, v, q_pos, k_pos, k_valid, causal=True,
                             window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    softcap=st.sampled_from([None, 10.0]),
)
def test_paged_decode_kernel_property(seed, softcap):
    """Fused paged decode == the materialised-gather oracle over random
    tables (sentinel 0 in dead entries) and ragged per-row lengths."""
    r = np.random.default_rng(seed)
    b, blk, n_max, kv, h, hd = 3, 8, 4, 2, 4, 16
    nb = n_max * b + 1
    pool_k = jnp.asarray(r.standard_normal((nb, blk, kv, hd)), jnp.float32)
    pool_v = jnp.asarray(r.standard_normal((nb, blk, kv, hd)), jnp.float32)
    tables = np.zeros((b, n_max), np.int32)
    lengths = np.zeros((b,), np.int32)
    ids = list(range(1, nb))
    r.shuffle(ids)
    for row in range(b):
        length = int(r.integers(1, n_max * blk + 1))
        lengths[row] = length
        n_live = -(-length // blk)
        tables[row, :n_live] = ids[:n_live]
        ids = ids[n_live:]
    q = jnp.asarray(r.standard_normal((b, 1, h, hd)), jnp.float32)
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    got = kattn.paged_decode_attention(q, pool_k, pool_v, tables, lengths,
                                       softcap=softcap, interpret=True)
    want = ref.paged_decode_ref(q, pool_k, pool_v, tables, lengths,
                                softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_matches_xla_gather_on_real_pool():
    """Token-level identity with the XLA lane on a REAL BlockPool table:
    allocate/free through the host accounting (so the table carries holes,
    sentinel entries, and out-of-order pool ids), then compare the fused
    kernel against decode_attention on the jnp.take gather."""
    r = np.random.default_rng(33)
    blk, n_max = 4, 6
    pool = BlockPool(num_blocks=16, block_size=blk)
    churn = [pool.alloc() for _ in range(5)]
    for bid in churn[::2]:
        pool.release(bid)  # punch holes so later allocs land out of order
    rows = []
    for length in (3, 9, 24, 1):
        n_live = -(-length // blk)
        tab = [pool.alloc() for _ in range(n_live)]
        rows.append((length, tab + [0] * (n_max - n_live)))
    tables = jnp.asarray([t for _, t in rows], jnp.int32)
    lengths = jnp.asarray([l for l, _ in rows], jnp.int32)
    b, kv, h, hd = len(rows), 2, 4, 8
    pool_k = jnp.asarray(r.standard_normal((16, blk, kv, hd)), jnp.float32)
    pool_v = jnp.asarray(r.standard_normal((16, blk, kv, hd)), jnp.float32)
    q = jnp.asarray(r.standard_normal((b, 1, h, hd)), jnp.float32)

    got = kattn.paged_decode_attention(q, pool_k, pool_v, tables, lengths,
                                       interpret=True)
    gk = jnp.take(pool_k, tables, axis=0).reshape(b, -1, kv, hd)
    gv = jnp.take(pool_v, tables, axis=0).reshape(b, -1, kv, hd)
    want = attn_lib.decode_attention(q, gk, gv, lengths, softcap=None,
                                     window=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_kernel_matches_xla_flash():
    """The two tiled lanes (Pallas vs lax.scan flash) agree on a block-
    aligned workload — attn_impl='pallas' is a drop-in for 'flash'."""
    r = np.random.default_rng(7)
    q, k, v = _qkv(r, 2, 64, 64, 4, 2, 16)
    for window, softcap in ((None, None), (16, 30.0)):
        got = kattn.flash_attention(q, k, v, True, window, softcap, 16, 16, True)
        want = attn_lib.flash_attention(q, k, v, True, window, softcap, 16, 16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_psgn_fused_matches_per_layer():
    """One fused launch over L stacked layers == the sum of per-layer
    oracles; the tree wrapper groups same-shape layers into it and the
    bias=True terms make probe norms exact for dense+bias models."""
    r = np.random.default_rng(11)
    L, b, s, di, do = 3, 4, 24, 10, 6
    xs = jnp.asarray(r.standard_normal((L, b, s, di)), jnp.float32)
    ds = jnp.asarray(r.standard_normal((L, b, s, do)), jnp.float32)
    from repro.kernels.psgn import psgn_fused

    got = psgn_fused(xs, ds, block_i=8, block_j=8, block_s=16, interpret=True)
    want = sum(ref.psgn_ref(xs[i], ds[i]) for i in range(L))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)

    acts = {f"l{i}": xs[i] for i in range(L)}
    dl = {f"l{i}": ds[i] for i in range(L)}
    tot = ops.persample_sq_norm_tree(acts, dl, scale=2.0, bias=True)
    want2 = sum(
        ref.psgn_ref(xs[i], ds[i] * 2.0)
        + jnp.sum(jnp.square(jnp.sum(ds[i] * 2.0, axis=1)), axis=-1)
        for i in range(L)
    )
    np.testing.assert_allclose(np.asarray(tot), np.asarray(want2), rtol=2e-5)


def test_default_interpret_and_none_flag():
    """Off-TPU the lane defaults to interpret mode, and interpret=None
    resolves through it (satellite: no more hard-coded interpret=True)."""
    assert default_interpret() is (jax.default_backend() != "tpu")
    x = _rand((2, 20, 12), jnp.float32)
    d = _rand((2, 20, 8), jnp.float32)
    got = ops.persample_sq_norm(x, d, interpret=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.psgn_ref(x, d)),
                               rtol=2e-5)
