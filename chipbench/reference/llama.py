"""Plain reference of a Llama-architecture decoder (Yi-6B is one), written
from the published description and independent of the code under test.

    h   = x + Attn(RMSNorm(x))          causal, grouped-query, rotary
    out = h + W_out(silu(W_gate h') * W_up h'),  h' = RMSNorm(h)
    logits = RMSNorm(out_L) @ W_head

Rotary embeddings rotate the two halves of each head (the Hugging Face
``rotate_half`` convention) with inverse frequencies theta^(-2i/hd); RMSNorm
uses the published epsilon.  Everything is computed in float32 with
``highest`` matmul precision, layer by layer (one layer's weights are
widened to float32 at a time, so a bf16 model of 12 GB fits beside them).

The weights are made here too, from the seed, in the tree layout the
system under test loads (``embed``, stacked ``pos0`` layers, ``final_norm``,
``lm_head``), so both sides start from the same numbers and this module
takes nothing the system has made.

``precision="fp8"`` is the control, the next precision below the bf16 the
configurations state: every matmul runs as fp8 training runs it, operands
rounded to float8 e4m3 and incoming gradients to e5m2, each tensor with its
own scale.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def dims_of(config: dict) -> dict:
    """The sizes this module uses, from a configuration file's published
    keys (Hugging Face ``config.json`` names)."""
    heads = config["num_attention_heads"]
    return {
        "num_layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "num_heads": heads,
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim", config["hidden_size"] // heads),
        "d_ff": config["intermediate_size"],
        "vocab_size": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "readout_scale": float(config["assumed"]["readout_scale"]),
    }


def weights_shapes(d: dict) -> dict:
    L, D, F, V = d["num_layers"], d["d_model"], d["d_ff"], d["vocab_size"]
    hd = d["head_dim"]
    H, KV = d["num_heads"], d["num_kv_heads"]
    return {
        "embed": {"embedding": (V, D)},
        "pos0": {
            "norm": {"scale": (L, D)},
            "attn": {"q": {"kernel": (L, D, H * hd)}, "k": {"kernel": (L, D, KV * hd)},
                     "v": {"kernel": (L, D, KV * hd)}, "o": {"kernel": (L, H * hd, D)}},
            "ffn_norm": {"scale": (L, D)},
            "ffn": {"w_gate": {"kernel": (L, D, F)}, "w_up": {"kernel": (L, D, F)},
                    "w_out": {"kernel": (L, F, D)}},
        },
        "final_norm": {"scale": (D,)},
        "lm_head": {"kernel": (D, V)},
    }


def _init_leaf(path: str, shape, key, readout: float):
    if path.endswith("scale"):
        return jnp.ones(shape, jnp.float32)
    if path.startswith("embed"):
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    fan_in = shape[-2]
    scale = 1.0 / np.sqrt(fan_in)
    if path.startswith("lm_head"):
        scale *= readout
    return jax.random.normal(key, shape, jnp.float32) * scale


@functools.lru_cache(maxsize=None)
def _init_jit(dims_items: tuple, dtype: str, readout: float):
    d = dict(dims_items)
    shapes = weights_shapes(d)
    flat, tree = jax.tree.flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))

    def init(key):
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, shape) in zip(keys, flat):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            leaves.append(_init_leaf(name, shape, k, readout).astype(dtype))
        return jax.tree.unflatten(tree, leaves)

    return jax.jit(init)


def make_weights(dims: dict, key, dtype: str):
    """All weights, made on the device in one jitted call from ``key``."""
    items = tuple(sorted((k, v) for k, v in dims.items() if isinstance(v, (int, float))))
    return _init_jit(items, dtype, float(dims.get("readout_scale", 1.0)))(key)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _q8(x, dtype=jnp.float8_e4m3fn):
    """Round to float8 with a per-tensor scale, back in float32."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = float(jnp.finfo(dtype).max) / amax
    return (x * s).astype(dtype).astype(jnp.float32) / s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(spec, a, b):
    """A float8 matmul as fp8 training runs one: operands in e4m3, the
    incoming gradient in e5m2, each with its own per-tensor scale."""
    return _einsum(spec, _q8(a), _q8(b))


def _mm8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _mm8_bwd(spec, res, g):
    _, vjp = jax.vjp(functools.partial(_einsum, spec), *res)
    return vjp(_q8(g, jnp.float8_e5m2))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(a, b, precision: str, spec: str = "...d,df->...f"):
    if precision == "fp8":
        return _mm8(spec, a, b)
    return _einsum(spec, a, b)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(d: dict, precision: str, x, lw):
    """One decoder layer on (B, S, D) float32 activations."""
    lw = jax.tree.map(lambda w: w.astype(jnp.float32), lw)
    b, s, _ = x.shape
    H, KV, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    eps, theta = d["rms_norm_eps"], d["rope_theta"]
    pos = jnp.arange(s)
    h = _rms(x, lw["norm"]["scale"], eps)
    a = lw["attn"]
    q = _mm(h, a["q"]["kernel"], precision).reshape(b, s, H, hd)
    k = _mm(h, a["k"]["kernel"], precision).reshape(b, s, KV, hd)
    v = _mm(h, a["v"]["kernel"], precision).reshape(b, s, KV, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    scores = _mm(q, k, precision, "bqhd,bkhd->bhqk") / np.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm(probs, v, precision, "bhqk,bkhd->bqhd").reshape(b, s, H * hd)
    x = x + _mm(o, a["o"]["kernel"], precision)
    h = _rms(x, lw["ffn_norm"]["scale"], eps)
    f = lw["ffn"]
    g = _mm(h, f["w_gate"]["kernel"], precision)
    u = _mm(h, f["w_up"]["kernel"], precision)
    return x + _mm(jax.nn.silu(g) * u, f["w_out"]["kernel"], precision)


def hidden(d: dict, w, tokens, precision: str = "f32"):
    """Final-normed hidden states (B, S, D) in float32."""
    x = w["embed"]["embedding"][tokens].astype(jnp.float32)
    body = jax.checkpoint(lambda x, lw: (_layer(d, precision, x, lw), None))
    x, _ = jax.lax.scan(body, x, w["pos0"])
    return _rms(x, w["final_norm"]["scale"].astype(jnp.float32), d["rms_norm_eps"])


def logits(d: dict, w, tokens, precision: str = "f32"):
    """(B, S, V) float32 logits at every position."""
    x = hidden(d, w, tokens, precision)
    return _mm(x, w["lm_head"]["kernel"].astype(jnp.float32), precision)


def loss(d: dict, w, tokens, targets, precision: str = "f32"):
    """Mean next-token cross-entropy."""
    x = hidden(d, w, tokens, precision)
    head = w["lm_head"]["kernel"].astype(jnp.float32)
    n_chunks = max(1, x.shape[1] // 512)

    def chunk_loss(xc, tc):
        lg = _mm(xc, head, precision)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - tgt)

    xs = jnp.split(x, n_chunks, axis=1)
    ts = jnp.split(targets, n_chunks, axis=1)
    total = sum(jax.checkpoint(chunk_loss)(xc, tc) for xc, tc in zip(xs, ts))
    return total / (x.shape[0] * x.shape[1])
