"""Pallas TPU kernels: per-sample gradient squared norms for dense layers.

For a dense layer y = x @ W applied over a sequence, the per-sample gradient
is G_b = X_b^T Delta_b (Din, Dout), where X_b is the saved input activation
and Delta_b the upstream output gradient (obtained for free via the probe
trick, DESIGN.md §3). DiveBatch needs ||G_b||_F^2 — never G_b itself.

Two factorisations, both avoiding the (B, Din, Dout) materialisation that
makes BackPACK double peak memory (paper Table 2):

  DIRECT  ||X^T D||_F^2 tile-by-tile: grid (B, Din/bi, Dout/bj, S/bs); an
          (bi, bj) f32 accumulator lives in VMEM scratch across the S-chunk
          axis (innermost, sequential on TPU) and is squared+reduced into the
          output on the last chunk. FLOPs ~ 2*S*Din*Dout per sample.
          MXU-aligned: bi = bj = 128, bs = 512.

  GRAM    sum_{t,t'} (x_t . x_t')(d_t . d_t') tile-by-tile over (S/bi, S/bj)
          pairs; both Gram blocks contract the full feature dim in one MXU
          pass. FLOPs ~ 2*S^2*(Din+Dout) per sample. Wins when
          S << Din*Dout/(Din+Dout).

ops.choose_method picks by FLOP count; ref.py is the pure-jnp oracle.
Kernels are VALIDATED in interpret mode on CPU (tests/test_kernels.py) and
target TPU for execution.

FUSED (psgn_fused) stacks L same-shape dense layers into one launch — grid
(B, L, Din/bi, Dout/bj, S/bs) — and accumulates the CROSS-LAYER sum straight
into one (8, 128) output tile per sample that stays resident across all
inner grid steps, so the exact diversity tier issues one kernel for a whole
probe tree instead of L separate launches with an XLA reduction after each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

# Partial sums leave a kernel as one broadcast (8, 128) f32 tile per grid
# step: the TPU lowering wants a block's last two dimensions aligned to that
# tiling, which a (1, 1) scalar block is not.
_TILE = (8, 128)


def _scalar_tile(x: jax.Array) -> jax.Array:
    """Full reduction of a 2-D block to a broadcast (8, 128) tile."""
    total = jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)
    return jnp.broadcast_to(total, _TILE)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


# ---------------------------------------------------------------------------
# DIRECT: grid (B, nI, nJ, nS), VMEM accumulator over the S axis
# ---------------------------------------------------------------------------


def _direct_kernel(x_ref, d_ref, o_ref, acc_ref, *, n_s: int):
    s = pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]  # (bs, bi)
    d = d_ref[0]  # (bs, bj)
    acc_ref[...] += jax.lax.dot_general(
        x, d, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(s == n_s - 1)
    def _finish():
        blk = acc_ref[...]
        o_ref[...] = _scalar_tile(blk * blk)


def psgn_direct(
    x: jax.Array,  # (B, S, Din)
    delta: jax.Array,  # (B, S, Dout)
    *,
    block_i: int = 128,
    block_j: int = 128,
    block_s: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """(B,) per-sample ||X_b^T Delta_b||_F^2 (f32)."""
    assert x.ndim == 3 and delta.ndim == 3 and x.shape[:2] == delta.shape[:2]
    b = x.shape[0]
    x = _pad_to(_pad_to(x, 2, block_i), 1, block_s)
    delta = _pad_to(_pad_to(delta, 2, block_j), 1, block_s)
    s, din = x.shape[1], x.shape[2]
    dout = delta.shape[2]
    n_i, n_j, n_s = din // block_i, dout // block_j, s // block_s

    grid = (b, n_i, n_j, n_s)
    partials = pl.pallas_call(
        functools.partial(_direct_kernel, n_s=n_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_i), lambda bb, i, j, ss: (bb, ss, i)),
            pl.BlockSpec((1, block_s, block_j), lambda bb, i, j, ss: (bb, ss, j)),
        ],
        out_specs=pl.BlockSpec((pl.Squeezed(),) * 3 + _TILE,
                               lambda bb, i, j, ss: (bb, i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_i, n_j) + _TILE, jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_i, block_j), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, delta)
    return partials[..., 0, 0].sum(axis=(1, 2))


# ---------------------------------------------------------------------------
# GRAM: grid (B, nSi, nSj); both Gram blocks contract full feature dims
# ---------------------------------------------------------------------------


def _gram_kernel(xi_ref, xj_ref, di_ref, dj_ref, o_ref):
    xi = xi_ref[0]  # (bi, Din)
    xj = xj_ref[0]  # (bj, Din)
    di = di_ref[0]  # (bi, Dout)
    dj = dj_ref[0]  # (bj, Dout)
    gx = jax.lax.dot_general(
        xi, xj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    gd = jax.lax.dot_general(
        di, dj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[...] = _scalar_tile(gx * gd)


def psgn_gram(
    x: jax.Array,
    delta: jax.Array,
    *,
    block_si: int = 256,
    block_sj: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """(B,) per-sample sum_{t,t'} (x_t.x_t')(d_t.d_t') == ||X^T D||_F^2."""
    assert x.ndim == 3 and delta.ndim == 3 and x.shape[:2] == delta.shape[:2]
    b = x.shape[0]
    x = _pad_to(x, 1, max(block_si, block_sj))
    delta = _pad_to(delta, 1, max(block_si, block_sj))
    s = x.shape[1]
    n_i, n_j = s // block_si, s // block_sj

    grid = (b, n_i, n_j)
    partials = pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_si, x.shape[2]), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((1, block_sj, x.shape[2]), lambda bb, i, j: (bb, j, 0)),
            pl.BlockSpec((1, block_si, delta.shape[2]), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((1, block_sj, delta.shape[2]), lambda bb, i, j: (bb, j, 0)),
        ],
        out_specs=pl.BlockSpec((pl.Squeezed(),) * 3 + _TILE,
                               lambda bb, i, j: (bb, i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_i, n_j) + _TILE, jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, x, delta, delta)
    return partials[..., 0, 0].sum(axis=(1, 2))


# ---------------------------------------------------------------------------
# FUSED: L stacked same-shape layers, one launch, cross-layer sum in-place
# ---------------------------------------------------------------------------


def _fused_kernel(x_ref, d_ref, o_ref, acc_ref, *, n_s: int):
    ll = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    s = pl.program_id(4)

    @pl.when(jnp.logical_and(jnp.logical_and(ll == 0, i == 0),
                             jnp.logical_and(j == 0, s == 0)))
    def _init_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0, 0]  # (bs, bi)
    d = d_ref[0, 0]  # (bs, bj)
    acc_ref[...] += jax.lax.dot_general(
        x, d, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(s == n_s - 1)
    def _finish():
        blk = acc_ref[...]
        o_ref[...] += _scalar_tile(blk * blk)


def psgn_fused(
    x: jax.Array,  # (L, B, S, Din) — L same-shape dense layers, stacked
    delta: jax.Array,  # (L, B, S, Dout)
    *,
    block_i: int = 128,
    block_j: int = 128,
    block_s: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """(B,) sum over the L layers of per-sample ||X^T D||_F^2, one launch.

    Sample b's (8, 128) output tile is revisited by every (l, i, j, s) step for a
    fixed b (the batch axis is outermost), so the cross-layer + cross-tile
    reduction happens in VMEM instead of as L separate XLA reductions.
    """
    assert x.ndim == 4 and delta.ndim == 4 and x.shape[:3] == delta.shape[:3]
    n_l, b = x.shape[0], x.shape[1]
    x = _pad_to(_pad_to(x, 3, block_i), 2, block_s)
    delta = _pad_to(_pad_to(delta, 3, block_j), 2, block_s)
    s, din = x.shape[2], x.shape[3]
    dout = delta.shape[3]
    n_i, n_j, n_s = din // block_i, dout // block_j, s // block_s

    grid = (b, n_l, n_i, n_j, n_s)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, n_s=n_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_s, block_i),
                         lambda bb, ll, i, j, ss: (ll, bb, ss, i)),
            pl.BlockSpec((1, 1, block_s, block_j),
                         lambda bb, ll, i, j, ss: (ll, bb, ss, j)),
        ],
        out_specs=pl.BlockSpec((pl.Squeezed(),) + _TILE,
                               lambda bb, ll, i, j, ss: (bb, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b,) + _TILE, jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_i, block_j), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, delta)
    return out[:, 0, 0]
