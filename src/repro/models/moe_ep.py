"""Expert-parallel MoE with EXPLICIT all-to-all (shard_map path).

§Perf Cell C4 (EXPERIMENTS.md): under pjit/GSPMD the index-based combine of
the capacity-dispatch MoE lowers to an all-gather of the full (E, C, d)
expert buffer — ~n_ep× the bytes an all-to-all needs. This module is the
production EP formulation: routing is shard-local, tokens travel to their
expert's shard and back via two `lax.all_to_all`s, expert GEMMs run on
resident weights, and the w_out contraction reduces over the tp axis with an
explicit psum.

Protocol per shard (T = local tokens, A = n_ep destination shards):
  1. route top-k; destination shard = expert // E_local
  2. scatter assignments into per-destination send buffers
     (A, CAP, d), CAP = ceil(T*k*cf/A); overflow drops (Switch-style)
  3. all_to_all  ->  (A, CAP, d) received tokens + their local-expert ids
  4. local capacity-dispatch to (E_local, C2, d); grouped GEMM
     (gate/up tp-sharded on ff; out reduces ff with psum over tp)
  5. gather results per received slot; all_to_all back; weighted combine.

Numerics match `moe.moe_reference` exactly when nothing is dropped
(tests/test_moe_ep.py sweeps this on an 8-device mesh).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.layers import ACTIVATIONS


def _positions_within(dest: jax.Array, num_dest: int) -> jax.Array:
    """0-based arrival order of each assignment at its destination bucket."""
    oh = jax.nn.one_hot(dest, num_dest, dtype=jnp.int32)  # (N, A)
    return jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1, dest[:, None], axis=1)[:, 0]


def _moe_ep_shard(
    x: jax.Array,  # (B_loc, S, d)
    router: jax.Array,  # (d, E)
    w_gate: jax.Array,  # (E_loc, d, ff_loc)
    w_up: jax.Array,
    w_out: jax.Array,  # (E_loc, ff_loc, d)
    *,
    top_k: int,
    capacity_factor: float,
    act: str,
    ep_axes,
    tp_axis: str,
    n_ep: int,
    e_total: int,
):
    act_fn = ACTIVATIONS[act]
    b, s, d = x.shape
    t = b * s
    e_loc = e_total // n_ep
    xt = x.reshape(t, d)

    # 1. route (fp32 router math, exactly as the GSPMD path)
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)  # (t, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    n = t * top_k
    e_flat = experts.reshape(n)
    w_flat = weights.reshape(n)
    token_idx = jnp.repeat(jnp.arange(t), top_k)
    dest = e_flat // e_loc  # destination ep shard
    e_local_id = e_flat % e_loc

    cap = max(int(t * top_k * capacity_factor / n_ep) + 1, 4)
    pos = _positions_within(dest, n_ep)
    keep = pos < cap
    # out-of-range rows drop (mode='drop'): dropped assignments never land
    row = jnp.where(keep, dest, n_ep)
    col = jnp.where(keep, pos, 0)

    send_x = jnp.zeros((n_ep, cap, d), x.dtype).at[row, col].add(
        xt[token_idx], mode="drop")
    send_e = jnp.full((n_ep, cap), -1, jnp.int32).at[row, col].set(
        e_local_id, mode="drop")

    # 2. exchange: slot [a] <- what shard a sent to me
    recv_x = jax.lax.all_to_all(send_x, ep_axes, 0, 0, tiled=True).reshape(
        n_ep * cap, d)
    recv_e = jax.lax.all_to_all(send_e[..., None], ep_axes, 0, 0,
                                tiled=True).reshape(n_ep * cap)

    # 3. local capacity dispatch to (E_loc, C2, d)
    t2 = n_ep * cap
    c2 = max(int(2.0 * t2 / e_loc) + 1, 4)
    valid = recv_e >= 0
    e_safe = jnp.where(valid, recv_e, 0)
    pos2 = _positions_within(jnp.where(valid, recv_e, e_loc), e_loc + 1)
    keep2 = valid & (pos2 < c2)
    row2 = jnp.where(keep2, e_safe, e_loc)
    col2 = jnp.where(keep2, pos2, 0)
    buf = jnp.zeros((e_loc, c2, d), x.dtype).at[row2, col2].add(
        recv_x, mode="drop")

    # 4. grouped GEMM on resident experts; ff is tp-sharded, so the w_out
    # contraction is a partial sum -> explicit psum over the tp axis
    h = act_fn(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
    y_buf = jnp.einsum("ecf,efd->ecd", h, w_out.astype(h.dtype))

    # 5. read back per received slot, THEN reduce the tp partial sums (the
    # gathered (t2, d) rows are ~3x smaller than the padded (E_loc, C2, d)
    # buffer), return exchange, weighted combine
    y_recv = y_buf[row2, col2] * keep2[:, None].astype(y_buf.dtype)
    y_recv = jax.lax.psum(y_recv, tp_axis)
    back = jax.lax.all_to_all(y_recv.reshape(n_ep, cap, d), ep_axes, 0, 0,
                              tiled=True)
    y_assign = back[row, col] * (w_flat * keep).astype(back.dtype)[:, None]
    y = jnp.zeros((t, d), x.dtype).at[token_idx].add(y_assign.astype(x.dtype))

    # aux load-balance loss (same definition as the GSPMD path), psum-averaged
    me = jax.nn.one_hot(experts[:, 0], e_total, dtype=jnp.float32).mean(0)
    pe = probs.mean(0)
    aux = e_total * jnp.sum(me * pe)
    aux = jax.lax.pmean(aux, ep_axes)
    return y.reshape(b, s, d), aux


def moe_apply_ep(
    params: dict,
    x: jax.Array,  # (B, S, d) global
    *,
    top_k: int,
    capacity_factor: float,
    act: str,
    mesh: Mesh,
    dp_axes: Sequence[str],
    ep_axes: Sequence[str],
    tp_axis: str,
) -> tuple[jax.Array, jax.Array]:
    """shard_map wrapper. Expert weights must be sharded E over ep_axes and
    ff over tp_axis (the standard rule table does this)."""
    e_total = params["w_gate"].shape[0]
    ep_axes = tuple(ep_axes)
    n_ep = 1
    for a in ep_axes:
        n_ep *= mesh.shape[a]
    dp = tuple(dp_axes)

    fn = functools.partial(
        _moe_ep_shard,
        top_k=top_k, capacity_factor=capacity_factor, act=act,
        ep_axes=ep_axes, tp_axis=tp_axis, n_ep=n_ep, e_total=e_total,
    )
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(dp, None, None),       # x: batch over dp, d replicated
            P(None, None),           # router replicated
            P(ep_axes, None, tp_axis),  # w_gate (E, d, ff)
            P(ep_axes, None, tp_axis),  # w_up
            P(ep_axes, tp_axis, None),  # w_out (E, ff, d)
        ),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )
    return mapped(x, params["router"]["kernel"], params["w_gate"],
                  params["w_up"], params["w_out"])
