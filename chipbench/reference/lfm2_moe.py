"""Plain reference of LFM2-MoE (LFM2-8B-A1B is one), written from the
published description (Hugging Face ``lfm2_moe``) and independent of the code
under test.  Each layer is

    h = x + mixer(RMSNorm(x))
    y = h + ffn(RMSNorm(h))

and the output is ``embedding_norm(y_L) @ E^T`` with the head tied to the
embedding ``E``.  The mixer is a gated short convolution or attention:

    conv       B, C, u = split3(x W_in);  y = (C * conv_k3(B * u)) W_out,
               conv_k3 causal and depthwise: y[t] = sum_i w[i] v[t - 2 + i]
    attention  grouped-query, q and k RMS-normed per head before the
               rotation (``rotate_half`` halves, theta^(-2i/hd)), causal

and the FFN is dense, ``W2(silu(W1 x) * W3 x)``, in the leading
``num_dense_layers`` layers and a mixture of experts after them:

    s = sigmoid(x W_g)                      over all routed experts
    E = top_k(s + expert_bias)              the bias selects only
    w_e = s_e / (sum_{e' in E} s_e' + 1e-6) * 1.0   for e in E
    y = sum_{e in E} w_e W2_e(silu(W1_e x) * W3_e x)

Only the experts this chip holds are computed (``held`` of ``routed``,
starting at ``first``): each densely for every token, masked by the
selection; the absent experts' part is left out, as in the program.

Everything runs in float32 with ``highest`` matmul precision, a layer at a
time under ``jax.checkpoint`` and attention a block of 1024 queries at a
time, so that one 8192-position row fits beside the model's state.  The
weights are made here, from the seed, in the tree the system under test
loads (``embed``, ``pos0`` .. ``pos{L-1}`` each holding its one layer on a
leading axis of 1, ``final_norm``), so both sides start from the same
numbers.  ``expert_bias`` is drawn N(0, 0.05^2), so that selection and
weighting differ (the published initial bias is zero).

``precision="fp8"`` is the control, the next precision below the bf16 the
configuration states: every matmul's operands rounded to float8 e4m3 and its
incoming gradient to e5m2, each tensor with its own scale (as
``llama.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.llama import _mm, _rope

Q_BLOCK = 1024  # queries per attention block


def dims_of(config: dict) -> dict:
    """The sizes this module uses, from a configuration file's published
    keys (Hugging Face ``config.json`` names).  ``num_experts`` is the
    experts held here; the router spans the published count."""
    heads = config["num_attention_heads"]
    layers = config["num_hidden_layers"]
    held = config["num_experts"]
    return {
        "num_layers": layers,
        "attention_layers": [i for i, t in enumerate(config["layer_types"][:layers])
                             if t == "full_attention"],
        "num_dense_layers": config["num_dense_layers"],
        "d_model": config["hidden_size"],
        "num_heads": heads,
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // heads,
        "d_ff": config["intermediate_size"],
        "d_expert": config["moe_intermediate_size"],
        "routed_experts": config.get("published", {}).get("num_experts", held),
        "held_experts": held,
        "top_k": config["num_experts_per_tok"],
        "conv_kernel": config["conv_L_cache"],
        "vocab_size": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["norm_eps"]),
    }


def weights_shapes(d: dict) -> dict:
    D, V, hd = d["d_model"], d["vocab_size"], d["head_dim"]
    H, KV, F, Fe = d["num_heads"], d["num_kv_heads"], d["d_ff"], d["d_expert"]
    E, Eh = d["routed_experts"], d["held_experts"]
    out = {"embed": {"embedding": (V, D)}, "final_norm": {"scale": (D,)}}
    for i in range(d["num_layers"]):
        if i in d["attention_layers"]:
            mixer = {"attn": {"q": {"kernel": (D, H * hd)}, "k": {"kernel": (D, KV * hd)},
                              "v": {"kernel": (D, KV * hd)}, "o": {"kernel": (H * hd, D)},
                              "q_norm": {"scale": (hd,)}, "k_norm": {"scale": (hd,)}}}
        else:
            mixer = {"conv": {"in_proj": {"kernel": (D, 3 * D)},
                              "conv": {"kernel": (d["conv_kernel"], D)},
                              "out_proj": {"kernel": (D, D)}}}
        if i < d["num_dense_layers"]:
            ffn = {"w_gate": {"kernel": (D, F)}, "w_up": {"kernel": (D, F)},
                   "w_out": {"kernel": (F, D)}}
        else:
            ffn = {"router": {"kernel": (D, E)}, "expert_bias": (E,),
                   "w_gate": (Eh, D, Fe), "w_up": (Eh, D, Fe), "w_out": (Eh, Fe, D)}
        layer = {"norm": {"scale": (D,)}, **mixer, "ffn_norm": {"scale": (D,)}, "ffn": ffn}
        out[f"pos{i}"] = jax.tree.map(lambda s: (1, *s), layer,
                                      is_leaf=lambda x: isinstance(x, tuple))
    return out


def _init_leaf(path: str, shape, key):
    if path.endswith("scale"):
        return jnp.ones(shape, jnp.float32)
    if path.startswith("embed"):
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    if path.endswith("expert_bias"):
        return jax.random.normal(key, shape, jnp.float32) * 0.05
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])


@functools.lru_cache(maxsize=None)
def _init_jit(dims_items: tuple, dtype: str):
    d = {k: list(v) if isinstance(v, tuple) else v for k, v in dims_items}
    flat, tree = jax.tree.flatten_with_path(weights_shapes(d),
                                            is_leaf=lambda x: isinstance(x, tuple))

    def init(key):
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, shape) in zip(keys, flat):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            leaves.append(_init_leaf(name, shape, k).astype(dtype))
        return jax.tree.unflatten(tree, leaves)

    return jax.jit(init)


def make_weights(dims: dict, key, dtype: str):
    """All weights, made on the device in one jitted call from ``key``."""
    items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in dims.items()))
    return _init_jit(items, dtype)(key)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _conv(d, precision, x, p):
    b_, c_, u = jnp.split(_mm(x, p["in_proj"]["kernel"], precision), 3, axis=-1)
    v = b_ * u
    w, s = p["conv"]["kernel"], x.shape[1]
    taps = w.shape[0]
    padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, i:i + s] * w[i] for i in range(taps))
    return _mm(c_ * y, p["out_proj"]["kernel"], precision)


def _attention(d, precision, x, p):
    b, s, _ = x.shape
    H, KV, hd, eps = d["num_heads"], d["num_kv_heads"], d["head_dim"], d["rms_norm_eps"]
    pos = jnp.arange(s)
    q = _rms(_mm(x, p["q"]["kernel"], precision).reshape(b, s, H, hd), p["q_norm"]["scale"], eps)
    k = _rms(_mm(x, p["k"]["kernel"], precision).reshape(b, s, KV, hd), p["k_norm"]["scale"], eps)
    v = _mm(x, p["v"]["kernel"], precision).reshape(b, s, KV, hd)
    q, k = _rope(q, pos, d["rope_theta"]), _rope(k, pos, d["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    qb = min(Q_BLOCK, s)

    @jax.checkpoint
    def block(args):
        qc, pc = args  # (b, qb, H, hd), (qb,)
        scores = _mm(qc, k, precision, "bqhd,bkhd->bhqk") / np.sqrt(hd)
        scores = jnp.where((pc[:, None] >= pos[None, :])[None, None], scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, axis=-1), v, precision, "bhqk,bkhd->bqhd")

    qs = jnp.moveaxis(q.reshape(b, s // qb, qb, H, hd), 1, 0)
    o = jax.lax.map(block, (qs, pos.reshape(s // qb, qb)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, H * hd)
    return _mm(o, p["o"]["kernel"], precision)


def _swiglu(precision, x, w_gate, w_up, w_out):
    return _mm(jax.nn.silu(_mm(x, w_gate, precision)) * _mm(x, w_up, precision), w_out,
               precision)


def _experts(d, precision, x, p):
    """The held experts' part of the mixture, each on every token."""
    scores = jax.nn.sigmoid(_mm(x, p["router"]["kernel"], precision))
    _, chosen = jax.lax.top_k(scores + p["expert_bias"], d["top_k"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    held = jnp.arange(d["held_experts"])
    gate = jnp.sum(jnp.where(chosen[..., None] == held, w[..., None], 0.0), axis=-2)
    y = 0.0
    for e in range(d["held_experts"]):
        y = y + gate[..., e:e + 1] * _swiglu(precision, x, p["w_gate"][e], p["w_up"][e],
                                              p["w_out"][e])
    return y


def _layer(d: dict, precision: str, i: int, x, lw):
    lw = jax.tree.map(lambda w: w[0].astype(jnp.float32), lw)
    eps = d["rms_norm_eps"]
    h = _rms(x, lw["norm"]["scale"], eps)
    if "attn" in lw:
        x = x + _attention(d, precision, h, lw["attn"])
    else:
        x = x + _conv(d, precision, h, lw["conv"])
    h = _rms(x, lw["ffn_norm"]["scale"], eps)
    f = lw["ffn"]
    if "router" in f:
        return x + _experts(d, precision, h, f)
    return x + _swiglu(precision, h, f["w_gate"]["kernel"], f["w_up"]["kernel"],
                       f["w_out"]["kernel"])


def hidden(d: dict, w, tokens, precision: str = "f32"):
    """Final-normed hidden states (B, S, D) in float32."""
    x = w["embed"]["embedding"][tokens].astype(jnp.float32)
    for i in range(d["num_layers"]):
        x = jax.checkpoint(functools.partial(_layer, d, precision, i))(x, w[f"pos{i}"])
    return _rms(x, w["final_norm"]["scale"].astype(jnp.float32), d["rms_norm_eps"])


def logits(d: dict, w, tokens, precision: str = "f32"):
    """(B, S, V) float32 logits at every position."""
    head = w["embed"]["embedding"].astype(jnp.float32).T
    return _mm(hidden(d, w, tokens, precision), head, precision)


def loss(d: dict, w, tokens, targets, precision: str = "f32"):
    """Mean next-token cross-entropy."""
    x = hidden(d, w, tokens, precision)
    head = w["embed"]["embedding"].astype(jnp.float32).T
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
