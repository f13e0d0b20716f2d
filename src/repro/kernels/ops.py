"""Jit-ready wrappers around the Pallas kernels with cost-model dispatch.

``repro.kernels.default_interpret`` is the one platform switch for the
whole kernel lane: compiled Pallas on TPU, interpret mode (pure-jax
emulation, still inside jit) everywhere else — tests exercise the real
kernel bodies on CPU.  Every kernel takes ``interpret=None`` by default and
resolves it through that switch; callers can force either mode by passing
``interpret=`` explicitly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import psgn as psgn_kernels
from repro.kernels import quant as quant_kernels


#: VMEM the gram kernel's streamed input blocks may take (of 16 MiB scoped)
_GRAM_VMEM = 12 << 20


def choose_method(s: int, d_in: int, d_out: int) -> str:
    """FLOP-count dispatch between the two per-sample-grad-norm kernels:
    direct ~ 2*S*Din*Dout, gram ~ 2*S^2*(Din+Dout)."""
    direct = 2.0 * s * d_in * d_out
    gram = 2.0 * s * s * (d_in + d_out)
    return "direct" if direct <= gram else "gram"


@functools.partial(jax.jit, static_argnames=("method", "interpret"))
def persample_sq_norm(
    x: jax.Array,  # (B, S, Din) or (B, Din)
    delta: jax.Array,  # (B, S, Dout) or (B, Dout)
    method: str = "auto",
    interpret: bool | None = None,
) -> jax.Array:
    """(B,) per-sample squared Frobenius norm of the dense-layer gradient.

    2D inputs (no sequence axis) factorise exactly:
    ||x_b delta_b^T||_F^2 = ||x_b||^2 * ||delta_b||^2 — no kernel needed.
    ``interpret=None`` resolves via ``default_interpret()``.
    """
    if x.ndim == 2:
        xn = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1)
        dn = jnp.sum(jnp.square(delta.astype(jnp.float32)), axis=-1)
        return xn * dn
    b, s, d_in = x.shape
    d_out = delta.shape[-1]
    if method == "auto":
        method = choose_method(s, d_in, d_out)
    if method == "direct":
        return psgn_kernels.psgn_direct(
            x, delta,
            block_s=min(512, _round_pow2(s)),
            block_i=min(128, _round_pow2(d_in)),
            block_j=min(128, _round_pow2(d_out)),
            interpret=interpret,
        )
    if method == "gram":
        # four (blk, features) input blocks, double-buffered, must fit the
        # kernel's scoped VMEM: at S=2048, Din+Dout=15104 (a 7B FFN layer) a
        # 256-row block needs 29.5 MiB of the 16 MiB a TPU kernel gets
        per_row = 4 * (d_in + d_out) * jnp.dtype(x.dtype).itemsize
        blk = min(256, _round_pow2(s), _round_pow2(max(_GRAM_VMEM // per_row, 8)))
        return psgn_kernels.psgn_gram(x, delta, block_si=blk, block_sj=blk,
                                      interpret=interpret)
    raise ValueError(f"unknown method {method!r}")


def _round_pow2(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _bias_sq_norm(d: jax.Array) -> jax.Array:
    """(B,) per-sample sq-norm of the BIAS gradient for the same layer: the
    per-sample bias grad is the sequence-sum of the output delta."""
    df = d.astype(jnp.float32)
    if df.ndim == 3:
        df = jnp.sum(df, axis=1)
    return jnp.sum(jnp.square(df), axis=-1)


def persample_sq_norm_tree(
    acts: dict,
    deltas: dict,
    scale: float = 1.0,
    *,
    bias: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Sum per-sample sq-norms over a dict of dense layers (gram-tier total).

    ``deltas`` are probe gradients of a MEAN loss — multiply by batch size
    (``scale``) to undo the 1/B factor.

    Layers whose shapes match and whose cost model picks the direct kernel
    are STACKED and dispatched to ``psgn.psgn_fused`` — one launch with the
    cross-layer sum fused in VMEM — instead of one launch per layer.
    ``bias=True`` adds each layer's bias-gradient sq-norm ``||sum_s d_s||^2``
    (exact for bias-complete dense models; probes see the same delta the
    bias does).
    """
    groups: dict[tuple, list[str]] = {}
    for name, x in acts.items():
        d = deltas[name]
        if x.ndim == 3 and choose_method(x.shape[1], x.shape[2], d.shape[2]) == "direct":
            key = (x.shape, d.shape, x.dtype, d.dtype)
        else:
            key = ("solo", name)
        groups.setdefault(key, []).append(name)

    total = None
    for key, names in groups.items():
        if key[0] != "solo" and len(names) >= 2:
            xs = jnp.stack([acts[n] for n in names])
            ds = jnp.stack([deltas[n] * scale for n in names])
            s, d_in = xs.shape[2], xs.shape[3]
            d_out = ds.shape[3]
            v = psgn_kernels.psgn_fused(
                xs, ds,
                block_s=min(512, _round_pow2(s)),
                block_i=min(128, _round_pow2(d_in)),
                block_j=min(128, _round_pow2(d_out)),
                interpret=interpret,
            )
        else:
            v = None
            for n in names:
                vi = persample_sq_norm(acts[n], deltas[n] * scale,
                                       interpret=interpret)
                v = vi if v is None else v + vi
        if bias:
            for n in names:
                v = v + _bias_sq_norm(deltas[n] * scale)
        total = v if total is None else total + v
    return total


quantize_int8 = quant_kernels.quantize_int8
dequantize_int8 = quant_kernels.dequantize_int8
