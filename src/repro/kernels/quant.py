"""Pallas TPU kernel: row-wise int8 quantisation (gradient compression).

Used by the cross-pod gradient compressor (dist/compression.py): gradients
crossing the slow DCN 'pod' axis are quantised to int8 with one f32 absmax
scale per row-block, with error feedback keeping SGD unbiased over time —
QSGD-style (Alistarh et al., cited by the paper as a diversity-increasing
technique that composes with DiveBatch).

Single fused pass: absmax-reduce + scale + round + cast, one read of the
input — the op is memory-bound, so fusing matters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # (block_r, C)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # (block_r, 1)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


#: VMEM the double-buffered input and int8 output blocks may take
_VMEM_BYTES = 8 << 20


def quantize_int8(
    x: jax.Array, *, block_rows: int = 256, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """x: (R, C) -> (q int8 (R, C), scales f32 (R,)).

    ``block_rows`` is capped so a block fits the kernel's VMEM at wide C
    (never below 32, the int8 row tiling).  The scales leave the kernel as an
    (R, 1) column: a 1-D block's layout does not match the one XLA gives a
    1-D array on TPU."""
    assert x.ndim == 2, x.shape
    r, c = x.shape
    fit = _VMEM_BYTES // (2 * c * (jnp.dtype(x.dtype).itemsize + 1))
    while block_rows > 32 and block_rows > fit:
        block_rows //= 2
    pad = (-r) % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rp = x.shape[0]
    grid = (rp // block_rows,)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rp, c), jnp.int8),
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        ),
        interpret=resolve_interpret(interpret),
    )(x)
    return q[:r], s[:r, 0]


def dequantize_int8(q: jax.Array, scales: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scales[:, None]).astype(dtype)
