"""Pallas TPU attention kernels: flash prefill + fused paged decode.

Three kernels behind ``cfg.attn_impl = "pallas"`` (models/transformer.py
dispatches; ``repro.kernels.default_interpret`` decides interpret mode):

  flash_attention   causal + sliding-window + softcap streaming-softmax
                    attention, matching ``models/attention.py::
                    flash_attention`` semantics (same scale, softcap-before-
                    mask order, NEG_INF bias, f32 accumulators).  custom_vjp
                    with the standard flash recompute backward — pass 1
                    re-streams KV blocks for dq, pass 2 re-streams Q blocks
                    for dk/dv — so the TRAIN path can adopt the kernel, not
                    just prefill.

  chunk_attention   the serving generalisation: queries at explicit absolute
                    positions over keys at explicit absolute positions with
                    a per-key validity mask (gathered pool blocks or a
                    windowed ring carry garbage rows that causality alone
                    cannot exclude).  Forward-only — decode never
                    differentiates.

  paged_decode_attention
                    single-token decode against the paged KV pool with the
                    BLOCK-TABLE GATHER FUSED INTO THE KV LOOP: the grid is
                    (B, n_max) and the k/v BlockSpec index_map reads the
                    scalar-prefetched table, so each step streams one POOL
                    block per row instead of materialising the
                    (B, n_max*block, KV, hd) gathered context the XLA path
                    builds with ``jnp.take``.  Per-row lengths mask the
                    sentinel/pool tail and ``pl.when`` skips dead table
                    entries entirely.  It reads the layer-stacked pool in
                    place: the layer index is a scalar prefetch too, so the
                    model's layer scan never slices a layer's pool out.

All kernels pad ragged shapes to block multiples internally (padding is
masked, outputs sliced); GQA is handled by mapping head h onto KV head
h // n_rep in the index_map.  The flash kernels run head-major: q/k/v are
transposed to ``(B, H, S, hd)`` so every block is a ``(block, hd)`` tile
with the batch and head dimensions squeezed, which is what the TPU lowering
asks of a block's last two dimensions; query positions travel as a
``(S, 1)`` column and key positions/validity as a ``(1, S)`` row, so the
mask broadcasts without a relayout.  ``kernels/ref.py`` holds the pure-jnp oracles
the property tests (tests/test_kernels.py) validate against in interpret
mode; TPU is the execution target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30  # matches models/attention.py


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value)


def _block_bias(q_pos, k_pos, q_valid, k_valid, causal, window):
    """(bq, bk) additive f32 bias — models/attention.py::_mask_bias plus
    explicit row/key validity (the padding / gathered-garbage mask).
    Query operands are (bq, 1) columns, key operands (1, bk) rows."""
    rel = q_pos - k_pos
    ok = q_valid & k_valid
    if causal:
        ok = ok & (rel >= 0)
    if window is not None:
        ok = ok & (rel < window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32), ok


# ---------------------------------------------------------------------------
# flash forward: grid (B, H, nQ, nK), streaming (m, l, acc) over the KV axis
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, kval_ref,
                      o_ref, lse_ref, m_ref, l_ref, acc_ref,
                      *, n_k, scale, causal, window, softcap):
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]  # (bq, hd)
    k = k_ref[...]  # (bk, hd)
    v = v_ref[...]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    q_pos = qp_ref[...]  # (bq, 1) int32 absolute positions
    k_pos = kp_ref[...]  # (1, bk)
    # positions are ABSOLUTE (chunk mode: unrelated to array indices), so
    # padding validity comes only from the sentinels: padded q rows carry a
    # negative position, padded/garbage keys carry k_valid = 0
    bias, _ = _block_bias(
        q_pos, k_pos, q_pos >= 0, kval_ref[...] > 0, causal, window,
    )
    logits = logits + bias
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l_safe)


def _head_major(x: jax.Array, block: int) -> jax.Array:
    """(B, S, H, hd) -> (B, H, S_padded, hd)."""
    return _pad_to(jnp.transpose(x, (0, 2, 1, 3)), 2, block)


def _pick(grid_axis, i, j):
    return i if grid_axis == 2 else j


def _tile(block: int, hd: int, grid_axis: int, n_rep: int = 1):
    """One (block, hd) tile of a head-major (B, H, S, hd) array whose S-block
    index is grid axis ``grid_axis`` (2 or 3 of the (B, H, ., .) grid);
    head h reads head h // n_rep (GQA)."""
    return pl.BlockSpec(
        (pl.Squeezed(), pl.Squeezed(), block, hd),
        lambda b_, h_, i, j: (b_, h_ // n_rep, _pick(grid_axis, i, j), 0),
    )


def _stat(block: int, grid_axis: int):
    """One (block, 1) column of a per-row statistic (B, H, S, 1)."""
    return pl.BlockSpec(
        (pl.Squeezed(), pl.Squeezed(), block, 1),
        lambda b_, h_, i, j: (b_, h_, _pick(grid_axis, i, j), 0),
    )


def _col(block: int, grid_axis: int):
    """One (block, 1) slice of an (S, 1) column (query positions)."""
    return pl.BlockSpec((block, 1), lambda b_, h_, i, j: (_pick(grid_axis, i, j), 0))


def _row(block: int, grid_axis: int):
    """One (1, block) slice of a (1, S) row (key positions / validity)."""
    return pl.BlockSpec((1, block), lambda b_, h_, i, j: (0, _pick(grid_axis, i, j)))


def _flash_forward(q, k, v, q_pos, k_pos, k_valid, causal, window, softcap,
                   q_block, kv_block, interpret):
    """Shared streaming forward. Positions/validity are host arrays sized to
    the unpadded seq lens; returns (out (B,Sq,H,hd), lse (B,H,Sqp,1))."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    n_rep = h // kv
    qt = _head_major(q, q_block)
    kt = _head_major(k, kv_block)
    vt = _head_major(v, kv_block)
    sqp, skp = qt.shape[2], kt.shape[2]
    n_q, n_k = sqp // q_block, skp // kv_block
    q_pos = _pad_to(q_pos.astype(jnp.int32), 0, q_block, value=-(2 ** 30))
    k_pos = _pad_to(k_pos.astype(jnp.int32), 0, kv_block)
    k_valid = _pad_to(k_valid.astype(jnp.int32), 0, kv_block)

    kernel = functools.partial(
        _flash_fwd_kernel, n_k=n_k, scale=hd ** -0.5, causal=causal,
        window=window, softcap=softcap,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            _tile(q_block, hd, 2),
            _tile(kv_block, hd, 3, n_rep),
            _tile(kv_block, hd, 3, n_rep),
            _col(q_block, 2),
            _row(kv_block, 3),
            _row(kv_block, 3),
        ],
        out_specs=[_tile(q_block, hd, 2), _stat(q_block, 2)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sqp, hd), q.dtype),
            jax.ShapeDtypeStruct((b, h, sqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt, q_pos[:, None], k_pos[None], k_valid[None])
    return jnp.transpose(out, (0, 2, 1, 3))[:, :sq], lse


# ---------------------------------------------------------------------------
# flash backward: standard recompute — pass 1 (dq), pass 2 (dk, dv)
# ---------------------------------------------------------------------------


def _recompute_dlogits(q, k, v, do, lse, delta, q_pos, k_pos, q_valid, k_valid,
                       scale, causal, window, softcap):
    """(p, dlogits) for one (bq, bk) tile; ``lse``/``delta`` are (bq, 1)
    columns.  Padded rows/keys force p = 0 explicitly: a padded q row's lse
    is garbage and exp() would overflow."""
    raw = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if softcap is not None:
        capped = jnp.tanh(raw / softcap)
        logits = capped * softcap
    else:
        logits = raw
    _, ok = _block_bias(q_pos, k_pos, q_valid, k_valid, causal, window)
    p = jnp.where(ok, jnp.exp(logits - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    dlogits = p * (dp - delta)
    if softcap is not None:
        dlogits = dlogits * (1.0 - capped * capped)
    return p, dlogits


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                     qp_ref, kp_ref, kval_ref, dq_ref, acc_ref,
                     *, n_k, scale, causal, window, softcap):
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos, k_pos = qp_ref[...], kp_ref[...]
    k = k_ref[...]
    _, dlogits = _recompute_dlogits(
        q_ref[...], k, v_ref[...], do_ref[...], lse_ref[...], dl_ref[...],
        q_pos, k_pos, q_pos >= 0, kval_ref[...] > 0,
        scale, causal, window, softcap,
    )
    acc_ref[...] += jax.lax.dot_general(
        dlogits.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale

    @pl.when(kj == n_k - 1)
    def _finish():
        dq_ref[...] = acc_ref[...]


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                      qp_ref, kp_ref, kval_ref, dk_ref, dv_ref,
                      dk_acc, dv_acc,
                      *, n_q, scale, causal, window, softcap):
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_pos, k_pos = qp_ref[...], kp_ref[...]
    q = q_ref[...]
    do = do_ref[...]
    p, dlogits = _recompute_dlogits(
        q, k_ref[...], v_ref[...], do, lse_ref[...], dl_ref[...],
        q_pos, k_pos, q_pos >= 0, kval_ref[...] > 0,
        scale, causal, window, softcap,
    )
    dv_acc[...] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dk_acc[...] += jax.lax.dot_general(
        dlogits.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[...] = dk_acc[...]
        dv_ref[...] = dv_acc[...]


def _flash_backward(q, k, v, out, lse, dout, causal, window, softcap,
                    q_block, kv_block, interpret):
    b, sq, h, hd = q.shape
    sk, kv_heads = k.shape[1], k.shape[2]
    n_rep = h // kv_heads
    scale = hd ** -0.5
    interpret = resolve_interpret(interpret)

    qt = _head_major(q, q_block)
    dot = _head_major(dout, q_block)
    kt = _head_major(k, kv_block)
    vt = _head_major(v, kv_block)
    sqp, skp = qt.shape[2], kt.shape[2]
    n_q, n_k = sqp // q_block, skp // kv_block
    # delta_i = rowsum(dout_i * out_i): (b, h, sqp, 1), like lse
    delta = _pad_to(
        jnp.einsum("bqhd,bqhd->bhq", dout.astype(jnp.float32),
                   out.astype(jnp.float32))[..., None], 2, q_block,
    )
    q_pos = _pad_to(jnp.arange(sq, dtype=jnp.int32), 0, q_block,
                    value=-(2 ** 30))[:, None]
    k_pos = _pad_to(jnp.arange(sk, dtype=jnp.int32), 0, kv_block)[None]
    k_valid = (k_pos < sk).astype(jnp.int32)
    args = (qt, kt, vt, dot, lse, delta, q_pos, k_pos, k_valid)

    # pass 1: dq — grid (B, H, nQ, nK), KV innermost
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, n_k=n_k, scale=scale, causal=causal,
                          window=window, softcap=softcap),
        grid=(b, h, n_q, n_k),
        in_specs=[
            _tile(q_block, hd, 2),
            _tile(kv_block, hd, 3, n_rep),
            _tile(kv_block, hd, 3, n_rep),
            _tile(q_block, hd, 2),
            _stat(q_block, 2),
            _stat(q_block, 2),
            _col(q_block, 2),
            _row(kv_block, 3),
            _row(kv_block, 3),
        ],
        out_specs=_tile(q_block, hd, 2),
        out_shape=jax.ShapeDtypeStruct((b, h, sqp, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((q_block, hd), jnp.float32)],
        interpret=interpret,
    )(*args)

    # pass 2: dk/dv — grid (B, H, nK, nQ), Q innermost; the repeated-head
    # gradients are folded back onto KV heads outside the kernel (GQA)
    dk_rep, dv_rep = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, n_q=n_q, scale=scale, causal=causal,
                          window=window, softcap=softcap),
        grid=(b, h, n_k, n_q),
        in_specs=[
            _tile(q_block, hd, 3),
            _tile(kv_block, hd, 2, n_rep),
            _tile(kv_block, hd, 2, n_rep),
            _tile(q_block, hd, 3),
            _stat(q_block, 3),
            _stat(q_block, 3),
            _col(q_block, 3),
            _row(kv_block, 2),
            _row(kv_block, 2),
        ],
        out_specs=[_tile(kv_block, hd, 2), _tile(kv_block, hd, 2)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, skp, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, skp, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kv_block, hd), jnp.float32),
                        pltpu.VMEM((kv_block, hd), jnp.float32)],
        interpret=interpret,
    )(*args)

    def fold(g):  # (B, H, Skp, hd) -> (B, Sk, KV, hd), summing each group
        g = g[:, :, :sk].reshape(b, kv_heads, n_rep, sk, hd).sum(2)
        return jnp.transpose(g, (0, 2, 1, 3))

    dq = jnp.transpose(dq[:, :, :sq], (0, 2, 1, 3)).astype(q.dtype)
    return dq, fold(dk_rep).astype(k.dtype), fold(dv_rep).astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas flash attention with the standard recompute backward.

    Semantics match ``models/attention.py::flash_attention`` (the XLA lane):
    scale ``hd**-0.5``, softcap applied BEFORE the mask bias, causal /
    sliding-window masking on absolute positions, f32 running (m, l, acc).
    Ragged Sq/Sk are padded to block multiples internally.
    """
    sq, sk = q.shape[1], k.shape[1]
    out, _ = _flash_forward(
        q, k, v, jnp.arange(sq, dtype=jnp.int32),
        jnp.arange(sk, dtype=jnp.int32), jnp.ones((sk,), jnp.int32),
        causal, window, softcap, q_block, kv_block, interpret,
    )
    return out


def _flash_vjp_fwd(q, k, v, causal, window, softcap, q_block, kv_block, interpret):
    sq, sk = q.shape[1], k.shape[1]
    out, lse = _flash_forward(
        q, k, v, jnp.arange(sq, dtype=jnp.int32),
        jnp.arange(sk, dtype=jnp.int32), jnp.ones((sk,), jnp.int32),
        causal, window, softcap, q_block, kv_block, interpret,
    )
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, window, softcap, q_block, kv_block, interpret, res, dout):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, dout, causal, window, softcap,
                           q_block, kv_block, interpret)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def chunk_attention(
    q: jax.Array,  # (B, C, H, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,
    q_pos: jax.Array,  # (C,) absolute positions of the queries
    k_pos: jax.Array,  # (Sk,) absolute positions of the keys
    k_valid: jax.Array,  # (Sk,) bool — False for padding/garbage key rows
    *,
    window: int | None = None,
    softcap: float | None = None,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas ``models/attention.py::chunk_attention``: causal attention at
    explicit positions with a key-validity mask (the paged chunked-prefill
    and windowed-ring layouts).  Forward-only."""
    out, _ = _flash_forward(
        q, k, v, q_pos, k_pos, k_valid.astype(jnp.int32),
        True, window, softcap, q_block, kv_block, interpret,
    )
    return out


# ---------------------------------------------------------------------------
# paged decode: the block-table gather fused into the streaming-softmax loop
# ---------------------------------------------------------------------------


def _paged_decode_kernel(tables_ref, lengths_ref, layer_ref, q_ref, k_ref,
                         v_ref, o_ref, m_ref, l_ref, acc_ref, *, blk, n_max,
                         softcap):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]

    # dead table entries (the sentinel/pool tail past this row's length)
    # contribute nothing — skip their FLOPs entirely
    @pl.when(i * blk < length)
    def _block():
        q = q_ref[0, 0]  # (H, hd)
        k = k_ref[0]     # (blk, KV, hd) — the table-gathered pool block
        v = v_ref[0]
        h, hd = q.shape
        kv = k.shape[1]
        n_rep = h // kv
        # GQA without materialising repeated heads: batch the dot over KV
        kt = jnp.transpose(k, (1, 0, 2))  # (KV, blk, hd)
        logits = jax.lax.dot_general(
            q.reshape(kv, n_rep, hd), kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(h, blk) * (hd ** -0.5)
        if softcap is not None:
            logits = jnp.tanh(logits / softcap) * softcap
        pos = i * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        logits = jnp.where(pos < length, logits, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        vt = jnp.transpose(v, (1, 0, 2))  # (KV, blk, hd)
        pv = jax.lax.dot_general(
            p.reshape(kv, n_rep, blk), vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * corr + pv.reshape(h, hd)
        m_ref[...] = m_new

    @pl.when(i == n_max - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,        # (B, 1, H, hd) — this step's query per slot
    pool_k: jax.Array,   # (L, num_blocks, block, KV, hd) — the SHARED pool,
                         # stacked over layers; or (num_blocks, block, KV, hd)
    pool_v: jax.Array,
    tables: jax.Array,   # (B, n_max) int32 — slot b's logical block i lives
                         # at pool block tables[b, i]; dead entries sentinel 0
    lengths: jax.Array,  # (B,) int32 — valid context length per slot
    layer: jax.Array | int | None = None,  # () int32 — which layer of the
                         # stack to read; None for a 4-D pool
    *,
    softcap: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused paged decode attention: (B, 1, H, hd).

    The XLA lane materialises ``pool[layer, tables]`` — the full
    (B, n_max*block, KV, hd) gathered context — before attending.  Here the
    gather IS the k/v BlockSpec index_map over the scalar-prefetched table:
    grid step (b, i) streams block ``tables[b, i]`` of layer ``layer``
    straight from the stacked pool, so only live blocks are read per row,
    and neither the gathered context nor the layer's slice of the pool ever
    exists in memory.  A 4-D pool is a one-layer stack.  Numerics match
    ``models/attention.py::decode_attention`` on the gathered view (same
    scale/softcap/length-mask order, f32 accumulation).
    """
    b, one, h, hd = q.shape
    assert one == 1, q.shape
    if pool_k.ndim == 4:
        assert layer is None, "a 4-D pool has no layer axis"
        pool_k, pool_v = pool_k[None], pool_v[None]
    layer = jnp.reshape(jnp.asarray(0 if layer is None else layer, jnp.int32),
                        (1,))
    _, nb, blk, kv, _ = pool_k.shape
    n_max = tables.shape[1]
    pool_spec = pl.BlockSpec(
        (pl.Squeezed(), 1, blk, kv, hd),
        lambda b_, i, t_, l_, ly_: (ly_[0], t_[b_, i], 0, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_max),
        in_specs=[
            pl.BlockSpec((1, 1, h, hd), lambda b_, i, t_, l_, ly_: (b_, 0, 0, 0)),
            pool_spec,
            pool_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, h, hd), lambda b_, i, t_, l_, ly_: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, blk=blk, n_max=n_max,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), layer, q, pool_k,
      pool_v)
