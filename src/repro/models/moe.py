"""Mixture-of-Experts FFN: top-k routing with capacity-based grouped GEMM.

Formulation chosen for GSPMD/multi-pod friendliness (DESIGN.md §5):
  * tokens are processed in ``groups`` (set to the data-parallel shard count)
    so routing (top-k, cumsum positions, scatter) is group-local — GSPMD
    partitions the group axis with zero communication;
  * per group, assignments are scattered into an (E, C, d) expert buffer; the
    expert GEMMs run as one grouped einsum over the expert axis. With expert
    weights sharded E->data and buffers G->data, GSPMD lowers the group<->
    expert transposition into the canonical MoE all-to-all;
  * capacity C = ceil(T_g * top_k * capacity_factor / E); overflow tokens are
    dropped (weight 0), Switch-style.

``moe_reference`` computes the same function densely (all experts for all
tokens) and is the correctness oracle in tests.

``held_moe_apply`` is the other expert layer, for ``router="sigmoid"``
(LFM2-MoE): the layer is told which consecutive experts it holds (its share
of an expert-parallel group), routes every token over ALL experts with
sigmoid scores, selects top-k by score plus a non-trained expert bias,
weights by the scores alone (renormalised over the k), and computes only
its own experts' part of the result.  It drops nothing under any
imbalance: the assignments to held experts are sorted by expert into a
buffer with room for every assignment, and the grouped matmuls
(kernels/gmm.py) visit the rows that are there, and at least a fixed row
budget: zero rows pad the last held expert's group up to it, so the
kernels' time does not follow how many assignments the routing sends here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.dist.plan import constrain
from repro.kernels.gmm import grouped_matmul
from repro.models.layers import ACTIVATIONS, dense_init


def moe_init(key, d_model: int, d_ff: int, num_experts: int, dtype=jnp.float32) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale_in = 1.0 / jnp.sqrt(d_model)
    scale_out = 1.0 / jnp.sqrt(d_ff)
    return {
        "router": dense_init(k1, d_model, num_experts, jnp.float32),
        "w_gate": (jax.random.normal(k2, (num_experts, d_model, d_ff)) * scale_in).astype(dtype),
        "w_up": (jax.random.normal(k3, (num_experts, d_model, d_ff)) * scale_in).astype(dtype),
        "w_out": (jax.random.normal(k4, (num_experts, d_ff, d_model)) * scale_out).astype(dtype),
    }


def _route(router_kernel: jax.Array, x: jax.Array, top_k: int):
    """x: (T, d) -> (weights (T,k), experts (T,k)); weights renormalised."""
    logits = (x.astype(jnp.float32) @ router_kernel.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, experts


def _capacity(tokens_per_group: int, top_k: int, num_experts: int, factor: float) -> int:
    c = int(tokens_per_group * top_k * factor / num_experts) + 1
    return max(c, 4)


def moe_apply(
    params: dict,
    x: jax.Array,  # (B, S, d) or (T, d)
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    groups: int = 1,
    act: str = "silu",
) -> tuple[jax.Array, jax.Array]:
    """Returns (output matching x's shape, auxiliary load-balance loss)."""
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    assert T % groups == 0, (T, groups)
    tg = T // groups
    E = params["w_gate"].shape[0]
    C = _capacity(tg, top_k, E, capacity_factor)
    act_fn = ACTIVATIONS[act]

    xg = xt.reshape(groups, tg, d)

    def per_group(xg_i):  # (tg, d)
        weights, experts = _route(params["router"]["kernel"], xg_i, top_k)  # (tg,k)
        # position of each assignment within its expert (Switch cumsum trick)
        oh = jax.nn.one_hot(experts.reshape(-1), E, dtype=jnp.int32)  # (tg*k, E)
        pos = (jnp.cumsum(oh, axis=0) - 1) * oh  # 0-based positions, only on hits
        pos_in_expert = pos.sum(axis=-1)  # (tg*k,)
        e_flat = experts.reshape(-1)
        w_flat = weights.reshape(-1)
        keep = pos_in_expert < C
        slot = jnp.where(keep, pos_in_expert, C - 1)
        token_idx = jnp.repeat(jnp.arange(tg), top_k)
        x_assign = xg_i[token_idx] * keep[:, None].astype(xg_i.dtype)
        buf = jnp.zeros((E, C, d), xg_i.dtype).at[e_flat, slot].add(x_assign)
        # load-balance aux (Switch eq. 4): E * sum_e f_e * p_e
        me = jax.nn.one_hot(experts[:, 0], E, dtype=jnp.float32).mean(0)
        pe = jax.nn.softmax(
            (xg_i.astype(jnp.float32) @ params["router"]["kernel"].astype(jnp.float32)),
            axis=-1,
        ).mean(0)
        aux = E * jnp.sum(me * pe)
        return buf, (e_flat, slot, w_flat, keep, token_idx), aux

    bufs, combine_info, aux = jax.vmap(per_group)(xg)  # bufs: (G, E, C, d)

    # EP boundary: reshard dispatch buffers group-major -> expert-major (the
    # canonical MoE all-to-all; without the constraint GSPMD was measured to
    # all-reduce the full (G,E,C,d) buffer instead), run the grouped GEMMs
    # expert-local, and reshard back for the combine.
    bufs = constrain(bufs, "moe_dispatch")
    h = act_fn(jnp.einsum("gecd,edf->gecf", bufs, params["w_gate"].astype(bufs.dtype)))
    h = h * jnp.einsum("gecd,edf->gecf", bufs, params["w_up"].astype(bufs.dtype))
    out_buf = jnp.einsum("gecf,efd->gecd", h, params["w_out"].astype(h.dtype))
    out_buf = constrain(out_buf, "moe_combine")

    def per_group_combine(out_buf_i, info):
        e_flat, slot, w_flat, keep, token_idx = info
        y_assign = out_buf_i[e_flat, slot]  # (tg*k, d)
        y_assign = y_assign * (w_flat * keep).astype(y_assign.dtype)[:, None]
        return jnp.zeros((tg, d), y_assign.dtype).at[token_idx].add(y_assign)

    yg = jax.vmap(per_group_combine)(out_buf, combine_info)  # (G, tg, d)
    return yg.reshape(orig_shape), aux.mean()


def moe_reference(params: dict, x: jax.Array, *, top_k: int, act: str = "silu") -> jax.Array:
    """Dense oracle: every expert computed for every token, then top-k mixed.
    No capacity limit — equals moe_apply exactly only when nothing is dropped
    (use capacity_factor high enough in tests)."""
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    weights, experts = _route(params["router"]["kernel"], xt, top_k)
    act_fn = ACTIVATIONS[act]
    h = act_fn(jnp.einsum("td,edf->tef", xt, params["w_gate"].astype(xt.dtype)))
    h = h * jnp.einsum("td,edf->tef", xt, params["w_up"].astype(xt.dtype))
    y_all = jnp.einsum("tef,efd->ted", h, params["w_out"].astype(h.dtype))  # (T,E,d)
    sel = jnp.take_along_axis(y_all, experts[:, :, None], axis=1)  # (T,k,d)
    y = (sel * weights[:, :, None].astype(sel.dtype)).sum(axis=1)
    return y.reshape(orig_shape)


# ---------------------------------------------------------------------------
# Dropless layer over a held share of sigmoid-routed experts
# ---------------------------------------------------------------------------

#: the step counters the layer reports: summed over layers and microbatches,
#: ``moe_max_expert_rows`` maxed
COUNTERS = ("moe_rows", "moe_max_expert_rows")

#: the grouped matmuls visit at least this multiple of the held experts'
#: expected rows (every assignment x held / routed experts); a layer whose
#: routing sends it more visits every one of them
ROW_BUDGET = 1.5


def held_moe_init(key, d_model: int, d_ff: int, num_experts: int, held: int,
                  dtype=jnp.float32) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale_in = 1.0 / jnp.sqrt(d_model)
    scale_out = 1.0 / jnp.sqrt(d_ff)
    return {
        "router": dense_init(k1, d_model, num_experts, dtype),
        "expert_bias": jnp.zeros((num_experts,), dtype),
        "w_gate": (jax.random.normal(k2, (held, d_model, d_ff)) * scale_in).astype(dtype),
        "w_up": (jax.random.normal(k3, (held, d_model, d_ff)) * scale_in).astype(dtype),
        "w_out": (jax.random.normal(k4, (held, d_ff, d_model)) * scale_out).astype(dtype),
    }


def route_sigmoid(params: dict, x: jax.Array, top_k: int):
    """x: (T, d) -> (weights (T, k) f32, experts (T, k)).  Selection by
    sigmoid score plus the expert bias, which takes no gradient; weights are
    the scores of the selected experts over (their sum + 1e-6), times the
    routed scale 1."""
    logits = jnp.dot(x.astype(jnp.float32), params["router"]["kernel"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(params["expert_bias"].astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    return weights / (weights.sum(-1, keepdims=True) + 1e-6), experts


@jax.custom_vjp
def _permute(x, idx, inv):
    """Rows ``x[idx]`` for a permutation ``idx`` whose inverse is ``inv``:
    the gradient is a gather too, not a scatter."""
    return x[idx]


def _permute_fwd(x, idx, inv):
    return x[idx], (idx, inv)


def _permute_bwd(res, g):
    idx, inv = res
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k):
    """Row r of the sorted buffer is token ``order[r] // k``'s input (each
    token repeated for its k assignments, without forming the repeat)."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, k):
    return x[order // k], (order, inv)


def _dispatch_bwd(k, res, g):
    order, inv = res
    gx = g[inv].astype(jnp.float32)
    return gx.reshape(-1, k, g.shape[-1]).sum(1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def held_moe_apply(params: dict, x: jax.Array, *, top_k: int, first: int = 0,
                   act: str = "silu", interpret: bool | None = None):
    """The held experts' part of the layer: x (B, S, d) -> (y, counters).

    ``params`` holds the router over every expert and the weights of the
    ``held`` experts ``first .. first + held - 1``.  ``counters``:
    ``moe_rows``, the assignments routed to the held experts, and
    ``moe_max_expert_rows``, the largest held expert's.  ``interpret`` goes
    to the grouped matmuls (None: the kernels on a TPU, the fallback
    elsewhere); they visit at least ``ROW_BUDGET`` x the expected held rows."""
    orig_shape, d = x.shape, x.shape[-1]
    xt = x.reshape(-1, d)
    n = xt.shape[0] * top_k
    held = params["w_gate"].shape[0]
    act_fn = ACTIVATIONS[act]
    with jax.named_scope("moe_route"):
        weights, experts = route_sigmoid(params, xt, top_k)
    with jax.named_scope("moe_dispatch"):
        local = experts.reshape(-1) - first
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held)  # the others sort last
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inv = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
        sizes = jnp.sum(jax.nn.one_hot(group, held + 1, dtype=jnp.int32), axis=0)[:held]
        rows = jnp.sum(sizes)
        # rows past the held assignments belong to no group, or to the last
        # expert's as zero padding up to the budget: the kernels leave the
        # first unwritten, so every buffer is masked past the held rows
        valid = (jnp.arange(n) < rows)[:, None]
        routed = params["router"]["kernel"].shape[-1]
        budget = min(n, math.ceil(ROW_BUDGET * n * held / routed))
        visit = sizes.at[held - 1].add(jnp.maximum(budget - rows, 0))
        xs = jnp.where(valid, _dispatch(xt, order, inv, top_k), 0)
    with jax.named_scope("moe_gmm"):
        w_gate, w_up, w_out = (params[k].astype(x.dtype) for k in ("w_gate", "w_up", "w_out"))
        g = jnp.where(valid, grouped_matmul(xs, w_gate, visit, interpret), 0)
        u = jnp.where(valid, grouped_matmul(xs, w_up, visit, interpret), 0)
        ys = jnp.where(valid, grouped_matmul(act_fn(g) * u, w_out, visit, interpret), 0)
    with jax.named_scope("moe_combine"):
        ya = _permute(ys, inv, order).reshape(-1, top_k, d).astype(jnp.float32)
        w = jnp.where(mine.reshape(-1, top_k), weights, 0.0)
        y = jnp.einsum("tkd,tk->td", ya, w).astype(x.dtype)
    counters = {"moe_rows": rows, "moe_max_expert_rows": jnp.max(sizes)}
    return y.reshape(orig_shape), counters

