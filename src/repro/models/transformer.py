"""The composable decoder/encoder stack covering every configured arch.

A model is (pattern x repeats) blocks; each pattern position has a mixer
('attn' | 'attn_local' | 'mamba' | 'conv') and an FFN kind ('dense' | 'moe'
| 'none').
Parameters for each pattern position are STACKED over the repeat axis R and
the stack runs as one ``lax.scan`` (+ optional ``jax.checkpoint``) — compile
time and HLO size are O(period), not O(num_layers), which is what makes the
126-layer 405B dry-run compile quickly.

Cross-entropy is CHUNKED over tokens (never materialises the (B,S,V) logits —
at vocab 256k that tensor alone would be ~0.5 TB for the train_4k cell).

Entry points:
  init_params(cfg, key)          parameter pytree (stacked blocks)
  loss_fn(cfg, params, batch)    scalar mean CE loss (+ MoE aux)
  forward(cfg, params, batch)    (B,S,V) f32 logits of one full pass
  prefill_step(cfg, params, batch)  -> (last_logits, cache)
  decode_step(cfg, params, cache, tokens) -> (logits, cache)
  init_cache(cfg, batch, seq_len)   cache pytree (or ShapeDtypeStructs via
                                    jax.eval_shape for the dry-run)
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.plan import constrain, current_plan, maps_shards, shard_local
from repro.dist.sharding import cache_pspecs, shardings_of
from repro.kernels import attention as kernels_attn
from repro.models import attention as attn_lib
from repro.models import conv as conv_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    ACTIVATIONS,
    apply_rope,
    dense,
    dense_init,
    embed,
    embed_init,
    layer_norm,
    norm_init,
    rms_norm,
)

PyTree = Any


def _norm(cfg: ModelConfig, params, x):
    if cfg.norm_type == "rms":
        return rms_norm(params, x, cfg.norm_eps)
    return layer_norm(params, x, cfg.norm_eps)


def _head(cfg: ModelConfig, params: PyTree) -> jax.Array:
    """The output head's (d_model, vocab) kernel."""
    if cfg.tie_embeddings:
        return params["embed"]["embedding"].T
    return params["lm_head"]["kernel"]


def step_counters(cfg: ModelConfig) -> tuple[str, ...]:
    """The step counters the model's layers report beside ``moe_aux``."""
    return moe_lib.COUNTERS if cfg.router == "sigmoid" and "moe" in cfg.ffn_pattern else ()


def _zero_stats(cfg: ModelConfig) -> dict:
    stats = {"moe_aux": jnp.zeros((), jnp.float32)}
    stats.update({k: jnp.zeros((), jnp.int32) for k in step_counters(cfg)})
    return stats


def _fold_stats(acc: dict, new: dict) -> dict:
    """Layer statistics summed over layers, the ``max`` ones maxed."""
    return {k: (jnp.maximum(v, new[k]) if "max" in k else v + new[k]) if k in new else v
            for k, v in acc.items()}


# Plan roles of each dim for the Pallas kernels (dist.plan.shard_local):
# (B, S, heads, hd) activations split the batch over dp and heads over tp;
# the paged KV pool, whole and stacked over layers (repeats, blocks, block,
# kv_heads, hd), splits only its heads, since a decode lane may read any
# block of any layer.
_HEADS = ("dp", None, "tp", None)
_POOL = (None, None, None, "tp", None)


def _flash_pallas(cfg: ModelConfig, q, k, v, causal: bool, window, s: int):
    def fn(q, k, v):
        return kernels_attn.flash_attention(
            q, k, v, causal, window, cfg.attn_softcap,
            min(cfg.flash_q_block, s), min(cfg.flash_kv_block, s),
        )

    return shard_local(fn, (q, k, v), (_HEADS,) * 3, _HEADS)


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_attn(cfg: ModelConfig, key) -> dict:
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = _pdtype(cfg)
    p = {
        "q": dense_init(k1, cfg.d_model, cfg.num_heads * hd, dt, use_bias=cfg.qkv_bias),
        "k": dense_init(k2, cfg.d_model, cfg.num_kv_heads * hd, dt, use_bias=cfg.qkv_bias),
        "v": dense_init(k3, cfg.d_model, cfg.num_kv_heads * hd, dt, use_bias=cfg.qkv_bias),
        "o": dense_init(k4, cfg.num_heads * hd, cfg.d_model, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd, dt)
        p["k_norm"] = norm_init(hd, dt)
    return p


def _init_ffn(cfg: ModelConfig, key, kind: str) -> dict:
    dt = _pdtype(cfg)
    if kind == "moe" and cfg.router == "sigmoid":
        return moe_lib.held_moe_init(key, cfg.d_model, cfg.expert_width, cfg.num_experts,
                                     cfg.held_experts, dt)
    if kind == "moe":
        return moe_lib.moe_init(key, cfg.d_model, cfg.expert_width, cfg.num_experts, dt)
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.ffn_glu:
        return {
            "w_gate": dense_init(k1, cfg.d_model, cfg.d_ff, dt),
            "w_up": dense_init(k2, cfg.d_model, cfg.d_ff, dt),
            "w_out": dense_init(k3, cfg.d_ff, cfg.d_model, dt),
        }
    return {
        "w_in": dense_init(k1, cfg.d_model, cfg.d_ff, dt),
        "w_out": dense_init(k3, cfg.d_ff, cfg.d_model, dt),
    }


def _init_block(cfg: ModelConfig, key, pos: int) -> dict:
    kind = cfg.pattern[pos]
    ffn_kind = cfg.ffn_kind(pos) if cfg.d_ff > 0 else "none"
    k_mix, k_ffn = jax.random.split(key)
    dt = _pdtype(cfg)
    block: dict = {"norm": norm_init(cfg.d_model, dt)}
    if kind in ("attn", "attn_local"):
        block["attn"] = _init_attn(cfg, k_mix)
    elif kind == "mamba":
        block["mamba"] = ssm_lib.mamba_init(
            k_mix, cfg.d_model, cfg.ssm_state, cfg.ssm_expand, cfg.ssm_conv,
            cfg.ssm_dt_rank, dt,
        )
    elif kind == "conv":
        block["conv"] = conv_lib.short_conv_init(k_mix, cfg.d_model, cfg.conv_kernel, dt)
    else:
        raise ValueError(f"unknown mixer kind {kind!r}")
    if ffn_kind != "none":
        block["ffn_norm"] = norm_init(cfg.d_model, dt)
        block["ffn"] = _init_ffn(cfg, k_ffn, ffn_kind)
    return block


def init_params(cfg: ModelConfig, key) -> PyTree:
    keys = jax.random.split(key, cfg.period + 3)
    dt = _pdtype(cfg)
    params: dict = {}
    if cfg.input_mode == "tokens":
        params["embed"] = embed_init(keys[-1], cfg.vocab_size, cfg.d_model, dt)
    else:
        params["frontend"] = dense_init(keys[-1], cfg.d_model, cfg.d_model, dt)
    for p in range(cfg.period):
        stack_keys = jax.random.split(keys[p], cfg.repeats)
        params[f"pos{p}"] = jax.vmap(lambda k: _init_block(cfg, k, p))(stack_keys)
    params["final_norm"] = norm_init(cfg.d_model, dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[-2], cfg.d_model, cfg.vocab_size, dt)
    return params


def _blocks(params: PyTree, cfg: ModelConfig) -> dict:
    return {f"pos{p}": params[f"pos{p}"] for p in range(cfg.period)}


# ---------------------------------------------------------------------------
# Block apply (single layer, full sequence)
# ---------------------------------------------------------------------------


def _qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    """Queries and keys (RMS-normed per head with ``qk_norm``, then rotated)
    and values of ``x`` (B, S, d) at ``positions``."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = dense(p["q"], x).reshape(b, s, cfg.num_heads, hd)
    k = dense(p["k"], x).reshape(b, s, cfg.num_kv_heads, hd)
    v = dense(p["v"], x).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_sublayer(cfg: ModelConfig, p: dict, x: jax.Array, kind: str,
                   positions: jax.Array) -> jax.Array:
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = _qkv(cfg, p, x, positions)
    window = cfg.window if kind == "attn_local" else None
    impl = attn_lib.resolve_impl(cfg, s)
    if impl == "pallas":
        out = _flash_pallas(cfg, q, k, v, cfg.causal, window, s)
    elif impl == "flash":
        out = attn_lib.flash_attention(
            q, k, v, cfg.causal, window, cfg.attn_softcap,
            min(cfg.flash_q_block, s), min(cfg.flash_kv_block, s),
        )
    else:
        out = attn_lib.attention(
            q, k, v, causal=cfg.causal, window=window, softcap=cfg.attn_softcap
        )
    return dense(p["o"], out.reshape(b, s, cfg.num_heads * hd))


def _ffn_sublayer(cfg: ModelConfig, p: dict, x: jax.Array, kind: str,
                  moe_groups: int) -> tuple[jax.Array, dict]:
    """The FFN's output and its layer statistics (``moe_aux``, or the
    ``step_counters`` of a held-share expert layer; none of a dense one)."""
    act = ACTIVATIONS[cfg.ffn_act]
    if kind == "moe" and cfg.router == "sigmoid":
        return moe_lib.held_moe_apply(p, x, top_k=cfg.top_k, act=cfg.ffn_act)
    if kind == "moe":
        plan = current_plan()
        if cfg.moe_impl == "ep" and plan is not None:
            from repro.models.moe_ep import moe_apply_ep

            y, aux = moe_apply_ep(
                p, x, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                act=cfg.ffn_act, mesh=plan.mesh, dp_axes=plan.dp,
                ep_axes=plan.ep, tp_axis=plan.tp,
            )
        else:
            y, aux = moe_lib.moe_apply(
                p, x, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                groups=moe_groups, act=cfg.ffn_act,
            )
        return y, {"moe_aux": aux}
    if cfg.ffn_glu:
        h = act(dense(p["w_gate"], x)) * dense(p["w_up"], x)
    else:
        h = act(dense(p["w_in"], x))
    return dense(p["w_out"], h), {}


def _block_apply(cfg: ModelConfig, pos: int, p: dict, x: jax.Array,
                 positions: jax.Array, moe_groups: int) -> tuple[jax.Array, dict]:
    kind = cfg.pattern[pos]
    h = _norm(cfg, p["norm"], x)
    if kind == "mamba":
        h = ssm_lib.mamba_apply(
            p["mamba"], h, d_state=cfg.ssm_state, dt_rank=cfg.dt_rank, chunk=cfg.ssm_chunk
        )
    elif kind == "conv":
        h = conv_lib.short_conv_apply(p["conv"], h)
    else:
        h = _attn_sublayer(cfg, p["attn"], h, kind, positions)
    x = x + h
    stats = _zero_stats(cfg)
    if "ffn" in p:
        h = _norm(cfg, p["ffn_norm"], x)
        h, new = _ffn_sublayer(cfg, p["ffn"], x=h, kind=cfg.ffn_kind(pos), moe_groups=moe_groups)
        stats.update(new)
        x = x + h
    return x, stats


# ---------------------------------------------------------------------------
# Stack
# ---------------------------------------------------------------------------


def _run_stack(cfg: ModelConfig, params: PyTree, x: jax.Array,
               positions: jax.Array, moe_groups: int) -> tuple[jax.Array, dict]:
    blocks = _blocks(params, cfg)

    # For multi-position patterns (gemma2 period 2, jamba period 8) remat each
    # BLOCK, not just the scan body: otherwise the backward of one scan step
    # holds `period` layers of intermediates live at once (measured 47 GiB on
    # jamba train_4k vs ~12 GiB with per-block remat).  A period that runs
    # once (lfm2's six layers, unrolled in one scan step) prevents CSE in each
    # block's checkpoint and takes none around the body: the TPU compile
    # otherwise held four expert layers' buffers live together.
    once = cfg.period > 1 and cfg.repeats == 1

    def apply_block(p, layer_p, h):
        if cfg.remat and cfg.period > 1:
            return jax.checkpoint(
                lambda lp, hh: _block_apply(cfg, p, lp, hh, positions, moe_groups),
                prevent_cse=once,
            )(layer_p, h)
        return _block_apply(cfg, p, layer_p, h, positions, moe_groups)

    def body(carry, layer):
        h, stats = carry
        h = constrain(h, "residual")
        for p in range(cfg.period):
            h, new = apply_block(p, layer[f"pos{p}"], h)
            stats = _fold_stats(stats, new)
        return (h, stats), None

    if cfg.remat and not once:
        body = jax.checkpoint(body, prevent_cse=False)

    if cfg.scan_layers:
        (x, stats), _ = jax.lax.scan(body, (x, _zero_stats(cfg)), blocks)
    else:
        stats = _zero_stats(cfg)
        for r in range(cfg.repeats):
            layer = jax.tree.map(lambda leaf: leaf[r], blocks)
            (x, stats), _ = body((x, stats), layer)
    return x, stats


def _embed_input(cfg: ModelConfig, params: PyTree, batch: dict) -> jax.Array:
    cdt = _cdtype(cfg)
    if cfg.input_mode == "tokens":
        return embed(params["embed"], batch["tokens"]).astype(cdt)
    return dense(params["frontend"], batch["embeddings"].astype(cdt))


# ---------------------------------------------------------------------------
# Loss (chunked cross-entropy)
# ---------------------------------------------------------------------------


def _chunk_ce(xc: jax.Array, kernel: jax.Array, tc: jax.Array, softcap):
    """One chunk's CE pieces. xc: (B,C,d); tc: (B,C). Returns (loss_sum, aux
    for backward): logits are formed in f32 and immediately reduced."""
    logits = (xc @ kernel.astype(xc.dtype)).astype(jnp.float32)
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


# The head's weight gradient is formed by dots of at least this many rows.
# Summing a dot into the f32 dW costs 8 bytes per element (read and write)
# against the dot's 2 x rows FLOPs per element: on a v5e (197 TFLOP/s bf16,
# 819 GB/s) that read-modify-write falls under half the dot's time from about
# 1,920 rows, while the bf16 dlogits held for one dot stay near rows x V.
# Rows are counted over the batch as traced (under a plan, the global batch).
XENT_DW_ROWS = 2048


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def xent_chunked(x: jax.Array, kernel: jax.Array, targets: jax.Array,
                 chunk: int = 512, softcap: float | None = None) -> jax.Array:
    """Mean token CE, chunked over the SEQUENCE axis so the (B,S,V) logits
    are never materialised (vocab 256k at train_4k would be ~0.5 TB).

    custom_vjp: the naive scan-under-grad would store every chunk's f32
    logits for the backward pass (measured 4e13 HBM bytes/step on qwen2);
    here the backward RECOMPUTES each chunk's logits, keeps only their
    dlogits in x's dtype, and forms dW from groups of chunks of at least
    ``XENT_DW_ROWS`` rows — residuals are just (x, kernel, targets).
    """
    b, s, d = x.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    def body(acc, i):
        xc = jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis=1)
        tc = jax.lax.dynamic_slice_in_dim(targets, i * chunk, chunk, axis=1)
        return acc + _chunk_ce(xc, kernel, tc, softcap), None

    loss_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), jnp.arange(nc))
    return loss_sum / (b * s)


def _xent_fwd(x, kernel, targets, chunk, softcap):
    return xent_chunked(x, kernel, targets, chunk, softcap), (x, kernel, targets)


def _xent_bwd(chunk, softcap, res, g):
    x, kernel, targets = res
    b, s, d = x.shape
    nc = s // min(chunk, s)
    chunk = min(chunk, s)
    v = kernel.shape[1]
    scale = g / (b * s)
    per_group = min(nc, -(-XENT_DW_ROWS // (b * chunk)))

    def chunk_grads(_, i):
        xc = jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis=1)
        tc = jax.lax.dynamic_slice_in_dim(targets, i * chunk, chunk, axis=1)
        logits = (xc @ kernel.astype(xc.dtype)).astype(jnp.float32)
        if softcap is not None:
            capped = jnp.tanh(logits / softcap)
            probs = jax.nn.softmax(capped * softcap, axis=-1)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
        dlogits = probs - jax.nn.one_hot(tc, v, dtype=jnp.float32)
        if softcap is not None:
            dlogits = dlogits * (1.0 - capped * capped)
        dlogits = (dlogits * scale).astype(x.dtype)
        dxc = dlogits @ kernel.astype(x.dtype).T
        return None, (dxc, dlogits)

    def group(first, n):
        """dx of chunks [first, first + n) and their one dot's share of dW."""
        _, (dxc, dlogits) = jax.lax.scan(chunk_grads, None, first + jnp.arange(n))
        xg = jax.lax.dynamic_slice_in_dim(x, first * chunk, n * chunk, axis=1)
        xg = jnp.moveaxis(xg.reshape(b, n, chunk, d), 1, 0)
        with jax.named_scope("xent_dw"):
            dw = jnp.einsum("nbcd,nbcv->dv", xg, dlogits,
                            preferred_element_type=jnp.float32)
        return dxc, dw

    full, rest = divmod(nc, per_group)
    if full == 1:
        dx_chunks, dw = group(0, per_group)
    else:
        def body(dw_acc, j):
            dxc, dw = group(j * per_group, per_group)
            return dw_acc + dw, dxc

        dw, dx_chunks = jax.lax.scan(body, jnp.zeros(kernel.shape, jnp.float32),
                                     jnp.arange(full))
        dx_chunks = dx_chunks.reshape(full * per_group, b, chunk, d)
    if rest:
        dxc, dw_rest = group(full * per_group, rest)
        dx_chunks, dw = jnp.concatenate([dx_chunks, dxc]), dw + dw_rest
    dx = jnp.moveaxis(dx_chunks, 0, 1).reshape(b, s, d)
    return dx, dw.astype(kernel.dtype), None


xent_chunked.defvjp(_xent_fwd, _xent_bwd)


def loss_fn(cfg: ModelConfig, params: PyTree, batch: dict,
            moe_groups: int = 1) -> tuple[jax.Array, dict]:
    x = _embed_input(cfg, params, batch)
    positions = jnp.arange(x.shape[1])[None, :]
    x, stats = _run_stack(cfg, params, x, positions, moe_groups)
    x = _norm(cfg, params["final_norm"], x)
    loss = xent_chunked(
        x, _head(cfg, params), batch["targets"], cfg.xent_chunk, cfg.final_softcap
    )
    metrics = {"ce_loss": loss, **stats}
    # the Switch balance term; the sigmoid router balances by its bias
    if cfg.num_experts and cfg.router == "softmax":
        loss = loss + 0.01 * stats["moe_aux"]
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: prefill + decode with per-pattern-position caches
# ---------------------------------------------------------------------------


def _cache_len_for(cfg: ModelConfig, pos: int, seq_len: int) -> int:
    if cfg.pattern[pos] == "attn_local" and cfg.window is not None:
        return min(cfg.window, seq_len)
    return seq_len


def refuse_serving(cfg: ModelConfig) -> None:
    """Serving keeps no short-convolution state: a 'conv' layer trains only."""
    if "conv" in cfg.pattern:
        raise NotImplementedError(
            f"{cfg.name}: 'conv' (short convolution) layers cannot be served yet: the "
            "serving cache has no place for their convolution state (ROADMAP Reach 3)")


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, *, skip: tuple = ()) -> PyTree:
    """Decode cache sized for a context of ``seq_len`` tokens.

    ``skip`` drops pattern positions from the tree — the paged serve path
    keeps full-attention KV in the block pool (``init_pages``) and only the
    O(1)-per-slot state (windowed rings, SSM state, lengths) stays dense."""
    refuse_serving(cfg)
    cdt = _cdtype(cfg)
    hd = cfg.resolved_head_dim
    cache: dict = {"len": jnp.zeros((), jnp.int32)}
    for p in range(cfg.period):
        if p in skip:
            continue
        kind = cfg.pattern[p]
        if kind == "mamba":
            d_inner = cfg.ssm_expand * cfg.d_model
            cache[f"pos{p}"] = {
                "h": jnp.zeros((cfg.repeats, batch, d_inner, cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((cfg.repeats, batch, cfg.ssm_conv - 1, d_inner), cdt),
            }
        else:
            s_c = _cache_len_for(cfg, p, seq_len)
            cache[f"pos{p}"] = {
                "k": jnp.zeros((cfg.repeats, batch, s_c, cfg.num_kv_heads, hd), cdt),
                "v": jnp.zeros((cfg.repeats, batch, s_c, cfg.num_kv_heads, hd), cdt),
            }
    return cache


def paged_positions(cfg: ModelConfig) -> tuple[int, ...]:
    """Pattern positions whose KV lives in the block pool when serving paged:
    the FULL-attention positions, whose per-slot cost would otherwise be
    O(max_seq).  Windowed rings and SSM state are already O(1) per slot and
    stay in the dense per-slot cache."""
    return tuple(p for p in range(cfg.period) if cfg.pattern[p] == "attn")


def init_pages(cfg: ModelConfig, num_blocks: int, block_size: int) -> PyTree:
    """The paged KV pool: per full-attention pattern position, a flat pool of
    ``num_blocks`` blocks of ``block_size`` token rows, stacked over repeats.
    The layer scans of ``decode_step`` and ``prefill_chunk`` carry it whole
    and update it in place, indexed by layer.  Block 0 is the sentinel —
    never allocated, the write target of inactive lanes (see
    serve/blocks.py)."""
    cdt = _cdtype(cfg)
    hd = cfg.resolved_head_dim
    return {
        f"pos{p}": {
            "k": jnp.zeros(
                (cfg.repeats, num_blocks, block_size, cfg.num_kv_heads, hd), cdt
            ),
            "v": jnp.zeros(
                (cfg.repeats, num_blocks, block_size, cfg.num_kv_heads, hd), cdt
            ),
        }
        for p in paged_positions(cfg)
    }


def decode_step(cfg: ModelConfig, params: PyTree, cache: PyTree,
                tokens_or_embs: jax.Array,
                moe_groups: int = 1, *,
                pages: PyTree | None = None,
                tables: jax.Array | None = None):
    """One token for every sequence in the batch. tokens: (B,1) int or
    (B,1,d) embeddings. Returns (logits (B,1,V), updated cache) — plus the
    updated pages when running paged.

    ``cache["len"]`` is either a scalar (every sequence at the same position
    — the classic lockstep-batch regime) or a ``(B,)`` vector of PER-SLOT
    positions (the ``repro.serve`` continuous-batching regime, where slots
    are admitted/retired independently and each row lives on its own
    timeline: RoPE, the ring-buffer write slot, and the validity mask are
    all per-row).

    Paged regime (``pages``/``tables`` given): full-attention positions read
    and write the block pool instead of a dense per-slot cache.  ``tables``
    is ``(B, n_max)`` int32 — slot b's logical block i lives at pool block
    ``tables[b, i]`` — so each row writes its current token at
    ``tables[b, pos // block]`` offset ``pos % block`` and reads its whole
    context through a table gather.  Inactive lanes point at sentinel block
    0 (written garbage, masked by the validity count on read).  The pool
    rides the layer scan's carry, one buffer for every layer: layer ``l``
    scatters into ``pages[..][l]`` and reads it in place, so a donated pool
    is neither sliced per layer nor restacked."""
    cdt = _cdtype(cfg)
    if cfg.input_mode == "tokens":
        x = embed(params["embed"], tokens_or_embs).astype(cdt)
    else:
        x = dense(params["frontend"], tokens_or_embs.astype(cdt))
    b = x.shape[0]
    pos_now = cache["len"]  # () int32, or (B,) int32 per-slot
    per_slot = jnp.ndim(pos_now) == 1
    hd = cfg.resolved_head_dim

    def layer_body(carry, scanned):
        x, pool = carry
        l, layer, lcache = scanned
        new_cache, pool = {}, dict(pool)
        for p in range(cfg.period):
            kind = cfg.pattern[p]
            blk = layer[f"pos{p}"]
            h = _norm(cfg, blk["norm"], x)
            if kind == "mamba":
                h, new_state = ssm_lib.mamba_decode_step(
                    blk["mamba"], lcache[f"pos{p}"], h,
                    d_state=cfg.ssm_state, dt_rank=cfg.dt_rank,
                )
                new_cache[f"pos{p}"] = new_state
            elif f"pos{p}" in pool:
                ap = blk["attn"]
                posv = (jnp.reshape(pos_now, (b,)) if per_slot
                        else jnp.full((b,), pos_now, jnp.int32))
                q, k, v = _qkv(cfg, ap, h, posv[:, None])
                pk, pv = pool[f"pos{p}"]["k"], pool[f"pos{p}"]["v"]
                blk_sz = pk.shape[2]
                rows = jnp.arange(b)
                wb = tables[rows, posv // blk_sz]  # (B,) pool block per row
                off = jnp.mod(posv, blk_sz)
                pk = pk.at[l, wb, off].set(k[:, 0])
                pv = pv.at[l, wb, off].set(v[:, 0])
                # write-then-read: this token is visible to its own query
                if cfg.attn_impl == "pallas":
                    # fused lane: the table gather happens inside the kernel's
                    # KV loop — the (B, n_max*block, KV, hd) gathered context
                    # below never materialises — and the kernel reads layer
                    # l of the stacked pool in place.  Mapped over shards,
                    # the kernel's pool operand is gathered whole onto every
                    # shard, so there it is this layer's slice, kept in the
                    # pool's own sharding (else the slice is cut from a
                    # gathered stack)
                    kv_l, at = {"k": pk, "v": pv}, l
                    if maps_shards():
                        kv_l = {n: jax.lax.dynamic_slice_in_dim(a, l, 1)
                                for n, a in kv_l.items()}
                        plan = current_plan()
                        kv_l = jax.lax.with_sharding_constraint(
                            kv_l, shardings_of(cache_pspecs(kv_l, plan), plan))
                        at = jnp.zeros((), jnp.int32)
                    h = shard_local(
                        functools.partial(kernels_attn.paged_decode_attention,
                                          softcap=cfg.attn_softcap),
                        (q, kv_l["k"], kv_l["v"], tables, posv + 1, at),
                        (_HEADS, _POOL, _POOL, ("dp", None), ("dp",), None),
                        _HEADS,
                    )
                else:
                    gk = pk[l, tables].reshape(b, -1, cfg.num_kv_heads, hd)
                    gv = pv[l, tables].reshape(b, -1, cfg.num_kv_heads, hd)
                    h = attn_lib.decode_attention(
                        q, gk, gv, posv + 1, softcap=cfg.attn_softcap, window=None,
                    )
                h = dense(ap["o"], h.reshape(b, 1, cfg.num_heads * hd))
                pool[f"pos{p}"] = {"k": pk, "v": pv}
            else:
                ap = blk["attn"]
                if per_slot:
                    posb = jnp.reshape(pos_now, (b, 1))
                else:
                    posb = jnp.full((b, 1), pos_now, jnp.int32)
                q, k, v = _qkv(cfg, ap, h, posb)
                s_c = lcache[f"pos{p}"]["k"].shape[1]
                slot = jnp.mod(pos_now, s_c)  # ring buffer for windowed layers
                if per_slot:
                    # each row writes at its own ring slot: a batched scatter
                    # touches B cache rows, not the whole (B, S, KV, hd) cache
                    rows = jnp.arange(b)
                    kc = lcache[f"pos{p}"]["k"].at[rows, slot].set(k[:, 0])
                    vc = lcache[f"pos{p}"]["v"].at[rows, slot].set(v[:, 0])
                else:
                    kc = jax.lax.dynamic_update_slice_in_dim(lcache[f"pos{p}"]["k"], k, slot, axis=1)
                    vc = jax.lax.dynamic_update_slice_in_dim(lcache[f"pos{p}"]["v"], v, slot, axis=1)
                n_valid = jnp.minimum(pos_now + 1, s_c)
                # Ring buffer: windowed layers size their cache to the window,
                # so every retained slot is attendable — mask only on validity.
                h = attn_lib.decode_attention(
                    q, kc, vc, n_valid, softcap=cfg.attn_softcap, window=None,
                )
                h = dense(ap["o"], h.reshape(b, 1, cfg.num_heads * hd))
                new_cache[f"pos{p}"] = {"k": kc, "v": vc}
            x = x + h
            if "ffn" in blk:
                h = _norm(cfg, blk["ffn_norm"], x)
                h, _ = _ffn_sublayer(cfg, blk["ffn"], x=h, kind=cfg.ffn_kind(p), moe_groups=moe_groups)
                x = x + h
        return (x, pool), new_cache

    blocks = _blocks(params, cfg)
    layer_caches = {k: v for k, v in cache.items() if k != "len"}
    (x, new_pages), new_caches = jax.lax.scan(
        layer_body, (x, pages if pages is not None else {}),
        (jnp.arange(cfg.repeats), blocks, layer_caches),
    )
    x = _norm(cfg, params["final_norm"], x)
    logits = (x @ _head(cfg, params).astype(x.dtype)).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    new_caches["len"] = cache["len"] + 1
    if pages is None:
        return logits, new_caches
    return logits, new_caches, new_pages


def forward(cfg: ModelConfig, params: PyTree, batch: dict,
            moe_groups: int = 1) -> jax.Array:
    """Logits (B, S, V) in f32 at every position of one full-sequence pass:
    the encoder's output, and the reference that the incremental serving
    path (prefill chunks, then decode steps) is checked against."""
    x = _embed_input(cfg, params, batch)
    positions = jnp.arange(x.shape[1])[None, :]
    x, _ = _run_stack(cfg, params, x, positions, moe_groups)
    x = _norm(cfg, params["final_norm"], x)
    logits = (x @ _head(cfg, params).astype(x.dtype)).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits


def prefill_step(cfg: ModelConfig, params: PyTree, batch: dict,
                 moe_groups: int = 1) -> tuple[jax.Array, PyTree]:
    """Encode a prompt; returns (last-position logits, populated cache).

    Encoder-only configs (causal=False) return full logits and no cache."""
    cdt = _cdtype(cfg)
    x = _embed_input(cfg, params, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    hd = cfg.resolved_head_dim

    if not cfg.causal:  # encoder: plain forward
        return forward(cfg, params, batch, moe_groups), {}

    def layer_body(carry, layer):
        x = carry
        new_cache = {}
        for p in range(cfg.period):
            kind = cfg.pattern[p]
            blk = layer[f"pos{p}"]
            h = _norm(cfg, blk["norm"], x)
            if kind == "mamba":
                # run the chunked scan and keep the final state for decode
                h_out, state = ssm_lib.mamba_apply(
                    blk["mamba"], h, d_state=cfg.ssm_state, dt_rank=cfg.dt_rank,
                    chunk=cfg.ssm_chunk, return_state=True,
                )
                new_cache[f"pos{p}"] = {
                    "h": state["h"],
                    "conv": state["conv"].astype(cdt),
                }
                h = h_out
            else:
                ap = blk["attn"]
                q, k, v = _qkv(cfg, ap, h, positions)
                window = cfg.window if kind == "attn_local" else None
                # honor cfg.attn_impl exactly like _attn_sublayer: "auto"
                # picks by length, a pinned impl is obeyed
                impl = attn_lib.resolve_impl(cfg, s)
                if impl == "pallas":
                    h = _flash_pallas(cfg, q, k, v, True, window, s)
                elif impl == "flash":
                    h = attn_lib.flash_attention(
                        q, k, v, True, window, cfg.attn_softcap,
                        min(cfg.flash_q_block, s), min(cfg.flash_kv_block, s),
                    )
                else:
                    h = attn_lib.attention(q, k, v, causal=True, window=window,
                                           softcap=cfg.attn_softcap)
                h = dense(ap["o"], h.reshape(b, s, cfg.num_heads * hd))
                s_c = _cache_len_for(cfg, p, s)
                new_cache[f"pos{p}"] = {
                    "k": k[:, -s_c:].astype(cdt),
                    "v": v[:, -s_c:].astype(cdt),
                }
            x = x + h
            if "ffn" in blk:
                h = _norm(cfg, blk["ffn_norm"], x)
                h, _ = _ffn_sublayer(cfg, blk["ffn"], x=h, kind=cfg.ffn_kind(p), moe_groups=moe_groups)
                x = x + h
        return x, new_cache

    body = layer_body
    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, caches = jax.lax.scan(body, x, _blocks(params, cfg))
    x = _norm(cfg, params["final_norm"], x)
    last = x[:, -1:, :]
    logits = (last @ _head(cfg, params).astype(last.dtype)).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    caches["len"] = jnp.full((), s, jnp.int32)
    return logits, caches


def prefill_chunk(cfg: ModelConfig, params: PyTree, row: PyTree, pages: PyTree,
                  batch: dict, offset: jax.Array, prior_tab: jax.Array,
                  write_tab: jax.Array, moe_groups: int = 1):
    """One chunk of a paged, resumable prefill for a SINGLE request.

    The prompt is fed in block-aligned chunks so a long prompt never stalls
    the running decode batch: the engine interleaves one chunk per request
    per boundary.  Each chunk attends to (a) the prior context gathered from
    the request's already-written pool blocks (full-attention positions) or
    its windowed ring / SSM state (carried in ``row``), and (b) its own keys
    — causally, at absolute positions ``offset + arange(C)``.

    Args:
      row: per-request carry — ``{"len": (1,)}`` plus windowed-ring and SSM
        entries (``init_cache(cfg, 1, max_seq, skip=paged_positions(cfg))``
        shapes); full-attention positions have NO row entry, their KV goes
        straight to ``pages`` at ``write_tab``.
      pages: the block pool (``init_pages`` layout), carried whole through
        the layer scan and updated in place, as in ``decode_step``.
      batch: ``{"tokens": (1, C)}`` — C a multiple of the block size.
      offset: () int32, this chunk's first absolute position (block-aligned).
      prior_tab: (nbp,) int32 prior prompt blocks in logical order, padded
        with sentinel 0 up to a pow2 length (so the compile key is
        ``(C, nbp, rung)``, not per-offset); entries past ``offset`` tokens
        are masked on read.
      write_tab: (C // block,) int32 destination blocks for this chunk.

    Returns (last-position logits (1,1,V), new row, new pages).
    """
    cdt = _cdtype(cfg)
    x = _embed_input(cfg, params, batch)
    _, c, _ = x.shape
    hd = cfg.resolved_head_dim
    positions = offset + jnp.arange(c)[None, :]  # (1, C)
    q_pos = offset + jnp.arange(c)
    paged = set(paged_positions(cfg))

    def _chunk_attn(q, k, v, k_pos, k_valid, window):
        # both prior-context layouts funnel through here; the pallas lane
        # runs the tiled kernel (pads ragged K internally), everything else
        # keeps the XLA chunk_attention
        if cfg.attn_impl == "pallas":
            fn = functools.partial(
                kernels_attn.chunk_attention, window=window,
                softcap=cfg.attn_softcap,
                q_block=min(cfg.flash_q_block, c),
                kv_block=min(cfg.flash_kv_block, k.shape[1]),
            )
            return shard_local(fn, (q, k, v, q_pos, k_pos, k_valid),
                               (_HEADS,) * 3 + (None,) * 3, _HEADS)
        return attn_lib.chunk_attention(
            q, k, v, q_pos, k_pos, k_valid, window=window,
            softcap=cfg.attn_softcap,
        )

    def layer_body(carry, scanned):
        x, pool = carry
        l, layer, lrow = scanned
        new_row, pool = {}, dict(pool)
        for p in range(cfg.period):
            kind = cfg.pattern[p]
            blk = layer[f"pos{p}"]
            h = _norm(cfg, blk["norm"], x)
            if kind == "mamba":
                # the internal chunked scan needs an even split; fall back to
                # one chunk when the prefill chunk doesn't divide
                sc = min(cfg.ssm_chunk, c)
                if c % sc:
                    sc = c
                h_out, state = ssm_lib.mamba_apply(
                    blk["mamba"], h, d_state=cfg.ssm_state, dt_rank=cfg.dt_rank,
                    chunk=sc, return_state=True, state=lrow[f"pos{p}"],
                )
                new_row[f"pos{p}"] = {
                    "h": state["h"],
                    "conv": state["conv"].astype(cdt),
                }
                h = h_out
            elif p in paged:  # full attention: prior context from the pool
                ap = blk["attn"]
                q, k, v = _qkv(cfg, ap, h, positions)
                pk, pv = pool[f"pos{p}"]["k"], pool[f"pos{p}"]["v"]
                blk_sz = pk.shape[2]
                # write-then-read, as in decode: the chunk's blocks are not
                # among its prior blocks, so the gather reads what it would
                # have before the write, and it reads the written pool, so
                # the write can go in place instead of into a copy.  Rows are
                # scattered one token at a time, as decode writes them: a
                # scatter of whole blocks leads the TPU compiler to lay the
                # pool out anew inside the scan, copying it in and out
                rows = jnp.arange(c)
                wb = write_tab[rows // blk_sz]
                off = jnp.mod(rows, blk_sz)
                pk = pk.at[l, wb, off].set(k[0])
                pv = pv.at[l, wb, off].set(v[0])
                pool[f"pos{p}"] = {"k": pk, "v": pv}
                np_prior = prior_tab.shape[0]
                prior = np_prior * blk_sz
                gk = pk[l, prior_tab].reshape(1, prior, cfg.num_kv_heads, hd)
                gv = pv[l, prior_tab].reshape(1, prior, cfg.num_kv_heads, hd)
                k_pos = jnp.concatenate([jnp.arange(prior), q_pos])
                k_valid = jnp.concatenate(
                    [jnp.arange(prior) < offset, jnp.ones((c,), bool)]
                )
                h = _chunk_attn(
                    q, jnp.concatenate([gk, k], axis=1),
                    jnp.concatenate([gv, v], axis=1),
                    k_pos, k_valid, window=None,
                )
                h = dense(ap["o"], h.reshape(1, c, cfg.num_heads * hd))
            elif kind == "attn_local":  # prior context from the windowed ring
                ap = blk["attn"]
                q, k, v = _qkv(cfg, ap, h, positions)
                ring_k, ring_v = lrow[f"pos{p}"]["k"], lrow[f"pos{p}"]["v"]
                s_c = ring_k.shape[1]
                prior_pos = offset - s_c + jnp.arange(s_c)  # chronological
                idx = jnp.mod(prior_pos, s_c)
                gk = jnp.take(ring_k, idx, axis=1)
                gv = jnp.take(ring_v, idx, axis=1)
                k_pos = jnp.concatenate([prior_pos, q_pos])
                k_valid = jnp.concatenate([prior_pos >= 0, jnp.ones((c,), bool)])
                h = _chunk_attn(
                    q, jnp.concatenate([gk, k], axis=1),
                    jnp.concatenate([gv, v], axis=1),
                    k_pos, k_valid, window=cfg.window,
                )
                h = dense(ap["o"], h.reshape(1, c, cfg.num_heads * hd))
                w = min(c, s_c)  # the chunk tail that survives into the ring
                widx = jnp.mod(offset + c - w + jnp.arange(w), s_c)
                ring_k = ring_k.at[:, widx].set(k[:, c - w:])
                ring_v = ring_v.at[:, widx].set(v[:, c - w:])
                new_row[f"pos{p}"] = {"k": ring_k, "v": ring_v}
            else:
                raise ValueError(
                    f"pattern position {p} ({kind!r}) has no paged-prefill path"
                )
            x = x + h
            if "ffn" in blk:
                h = _norm(cfg, blk["ffn_norm"], x)
                h, _ = _ffn_sublayer(cfg, blk["ffn"], x=h, kind=cfg.ffn_kind(p), moe_groups=moe_groups)
                x = x + h
        return (x, pool), new_row

    blocks = _blocks(params, cfg)
    row_layers = {k: v for k, v in row.items() if k != "len"}
    (x, new_pages), new_row = jax.lax.scan(
        layer_body, (x, pages), (jnp.arange(cfg.repeats), blocks, row_layers)
    )
    x = _norm(cfg, params["final_norm"], x)
    last = x[:, -1:, :]
    logits = (last @ _head(cfg, params).astype(last.dtype)).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    new_row["len"] = row["len"] + c
    return logits, new_row, new_pages


# ---------------------------------------------------------------------------
# Shape stand-ins (dry-run)
# ---------------------------------------------------------------------------


def param_specs(cfg: ModelConfig) -> PyTree:
    """ShapeDtypeStruct tree of the parameters — no allocation."""
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> PyTree:
    return jax.eval_shape(lambda: init_cache(cfg, batch, seq_len))
