"""Gated short convolution, the mixer of LFM2's 'conv' layers.

    B, C, u = split3(x W_in)
    y       = (C * causal_depthwise_conv(B * u)) W_out

The depthwise convolution runs along the sequence with a kernel of
``L`` taps per channel: ``conv(v)[t] = sum_i w[i] * v[t - (L - 1) + i]``,
zero before the first position (PyTorch's ``Conv1d(groups=D,
padding=L-1)`` cut to the sequence).  No biases.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense, dense_init


def short_conv_init(key, d_model: int, kernel: int, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "in_proj": dense_init(k1, d_model, 3 * d_model, dtype),
        "conv": {"kernel": (jax.random.normal(k2, (kernel, d_model))
                            / jnp.sqrt(kernel)).astype(dtype)},
        "out_proj": dense_init(k3, d_model, d_model, dtype),
    }


def causal_conv(v: jax.Array, w: jax.Array) -> jax.Array:
    """(B, S, D) by per-channel taps (L, D), causal, in float32."""
    taps, s = w.shape[0], v.shape[1]
    v = v.astype(jnp.float32)
    w = w.astype(jnp.float32)
    padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * w[i] for i in range(taps))


def short_conv_apply(params: dict, x: jax.Array) -> jax.Array:
    with jax.named_scope("short_conv"):
        b, c, u = jnp.split(dense(params["in_proj"], x), 3, axis=-1)
        y = c * causal_conv(b * u, params["conv"]["kernel"]).astype(x.dtype)
        return dense(params["out_proj"], y)
