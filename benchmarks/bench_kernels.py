"""Kernel-lane microbenchmarks: the Pallas attention + psgn kernels vs
their XLA counterparts (interpret mode on CPU — timings are
correctness-path numbers; the derived column carries the structural
FLOP/bytes-moved model that holds on TPU).  Writes ``BENCH_kernels.json``.

The headline row is the FUSED paged decode: the XLA lane materialises the
``jnp.take(pool, tables)`` gather — every slot's table window, dead tail
included, written to a fresh (B, n_max*block, KV, hd) buffer and then
re-read by attention — while the Pallas kernel streams pool blocks through
the BlockSpec index_map and never materialises the gathered context.  The
bytes-moved model for both lanes is computed here and the fused lane is
ASSERTED to move fewer bytes (the PR's acceptance invariant).

  PYTHONPATH=src python -m benchmarks.bench_kernels [--smoke] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import attention as kattn
from repro.kernels import default_interpret, ops, psgn, ref
from repro.kernels.quant import quantize_int8
from repro.models import attention as attn_lib

_DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")

PSGN_SHAPES = [
    (4, 256, 256, 256),
    (2, 512, 128, 512),
    (8, 128, 512, 64),
]


def _time(fn, *args, reps=3):
    fn(*args)  # compile/warm
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def _psgn_rows(rng, smoke: bool):
    rows = []
    shapes = PSGN_SHAPES[:1] if smoke else PSGN_SHAPES
    for b, s, di, do in shapes:
        x = jnp.asarray(rng.standard_normal((b, s, di)), jnp.float32)
        d = jnp.asarray(rng.standard_normal((b, s, do)), jnp.float32)
        t_ref = _time(lambda a, c: ref.psgn_ref(a, c), x, d)
        t_dir = _time(lambda a, c: ops.persample_sq_norm(a, c, method="direct"), x, d)
        t_gram = _time(lambda a, c: ops.persample_sq_norm(a, c, method="gram"), x, d)
        flops_direct = 2 * b * s * di * do
        flops_gram = 2 * b * s * s * (di + do)
        # bytes the ORACLE materialises that the kernels never do
        oracle_bytes = b * di * do * 4
        rows.append((
            f"psgn_direct_b{b}s{s}_{di}x{do}", t_dir * 1e6,
            f"flops={flops_direct:.3g};oracle_materialises={oracle_bytes}B;"
            f"ref_us={t_ref*1e6:.0f}",
        ))
        rows.append((
            f"psgn_gram_b{b}s{s}_{di}x{do}", t_gram * 1e6,
            f"flops={flops_gram:.3g};chosen={ops.choose_method(s, di, do)}",
        ))
    # fused multi-layer launch: L same-shape layers in ONE kernel vs L
    # separate persample_sq_norm launches
    L, b, s, di, do = (2, 2, 128, 64, 64) if smoke else (4, 4, 256, 128, 128)
    xs = jnp.asarray(rng.standard_normal((L, b, s, di)), jnp.float32)
    ds = jnp.asarray(rng.standard_normal((L, b, s, do)), jnp.float32)
    t_fused = _time(lambda a, c: psgn.psgn_fused(a, c), xs, ds)
    t_loop = _time(
        lambda a, c: sum(
            ops.persample_sq_norm(a[i], c[i], method="direct") for i in range(L)
        ),
        xs, ds,
    )
    rows.append((
        f"psgn_fused_L{L}_b{b}s{s}_{di}x{do}", t_fused * 1e6,
        f"launches=1_vs_{L};per_layer_us={t_loop*1e6:.0f}",
    ))
    return rows


def _flash_rows(rng, smoke: bool):
    b, s, h, kv, hd = (2, 128, 4, 2, 32) if smoke else (2, 256, 8, 2, 64)
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, hd)), jnp.float32)
    blk = 64 if smoke else 128
    # reps=1: interpret mode walks the grid in python — one measured call
    # is representative and keeps the full bench bounded
    t_pal = _time(lambda *a: kattn.flash_attention(*a, True, None, None, blk, blk),
                  q, k, v, reps=1)
    t_xla = _time(lambda *a: attn_lib.flash_attention(*a, True, None, None, blk, blk),
                  q, k, v)
    t_dense = _time(lambda *a: attn_lib.attention(*a, causal=True), q, k, v)
    # streaming softmax never materialises the (b, h, s, s) score matrix
    dense_scores = b * h * s * s * 4
    flash_live = b * h * blk * blk * 4
    rows = [(
        f"flash_pallas_b{b}s{s}h{h}", t_pal * 1e6,
        f"xla_flash_us={t_xla*1e6:.0f};xla_dense_us={t_dense*1e6:.0f};"
        f"dense_scores={dense_scores}B;live_tile={flash_live}B",
    )]
    # backward: the recompute custom_vjp vs XLA flash autodiff
    loss_p = lambda *a: jnp.sum(
        jnp.sin(kattn.flash_attention(*a, True, None, None, blk, blk)))
    loss_x = lambda *a: jnp.sum(
        jnp.sin(attn_lib.flash_attention(*a, True, None, None, blk, blk)))
    t_pb = _time(jax.jit(jax.grad(loss_p, argnums=(0, 1, 2))), q, k, v, reps=1)
    t_xb = _time(jax.jit(jax.grad(loss_x, argnums=(0, 1, 2))), q, k, v)
    rows.append((
        f"flash_pallas_bwd_b{b}s{s}h{h}", t_pb * 1e6,
        f"xla_flash_bwd_us={t_xb*1e6:.0f};recompute=fwd_logits",
    ))
    return rows


def _paged_bytes(lengths, n_max, blk, kv, hd, itemsize=4):
    """Bytes-moved model for one decode step over the KV pool (k + v).

    fused: the kernel DMAs exactly the live blocks of each row straight from
    the pool (dead-tail grid steps hit the sentinel block, which stays
    resident).  materialised: the XLA lane reads the same pool rows, then
    WRITES the full (B, n_max*block) gathered buffer — dead tail included —
    and attention RE-READS it."""
    per_block = blk * kv * hd * itemsize * 2  # k and v
    live = sum(-(-int(l) // blk) for l in lengths)
    fused = live * per_block
    gathered = len(lengths) * n_max * per_block
    materialised = live * per_block + 2 * gathered  # read pool + write + re-read
    return fused, materialised


def _paged_rows(rng, smoke: bool):
    blk, kv, h, hd = 16, 2, 4, 32
    b, n_max, num_blocks = (4, 4, 32) if smoke else (8, 8, 128)
    pool_k = jnp.asarray(rng.standard_normal((num_blocks, blk, kv, hd)), jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((num_blocks, blk, kv, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.float32)
    # ragged lengths — most rows use a fraction of their table window
    lengths = rng.integers(1, n_max * blk, size=b)
    tables = np.zeros((b, n_max), np.int32)
    ids = rng.permutation(np.arange(1, num_blocks))
    nxt = 0
    for r in range(b):
        for i in range(-(-int(lengths[r]) // blk)):
            tables[r, i] = ids[nxt]
            nxt += 1
    tables_j = jnp.asarray(tables)
    lengths_j = jnp.asarray(lengths, jnp.int32)

    t_fused = _time(
        lambda *a: kattn.paged_decode_attention(*a),
        q, pool_k, pool_v, tables_j, lengths_j, reps=1,
    )

    @jax.jit
    def xla_gather(q, pk, pv, t, ln):
        kc = jnp.take(pk, t, axis=0).reshape(b, n_max * blk, kv, hd)
        vc = jnp.take(pv, t, axis=0).reshape(b, n_max * blk, kv, hd)
        return attn_lib.decode_attention(q, kc, vc, ln)

    t_mat = _time(xla_gather, q, pool_k, pool_v, tables_j, lengths_j)

    fused_b, mat_b = _paged_bytes(lengths, n_max, blk, kv, hd)
    # the acceptance invariant: fusing the gather into the KV loop moves
    # measurably fewer bytes than materialise-then-attend
    assert fused_b < mat_b, (fused_b, mat_b)
    rows = [(
        f"paged_decode_fused_b{b}n{n_max}blk{blk}", t_fused * 1e6,
        f"materialised_us={t_mat*1e6:.0f};fused_bytes={fused_b};"
        f"materialised_bytes={mat_b};bytes_ratio={fused_b/mat_b:.3f}",
    )]
    record = {
        "batch": b, "n_max": n_max, "block": blk, "kv_heads": kv,
        "head_dim": hd, "lengths": [int(x) for x in lengths],
        "fused_us": round(t_fused * 1e6, 1),
        "materialised_us": round(t_mat * 1e6, 1),
        "fused_bytes": fused_b, "materialised_bytes": mat_b,
        "bytes_ratio": round(fused_b / mat_b, 4),
    }
    return rows, record


def _quant_row(rng):
    g = jnp.asarray(rng.standard_normal((1024, 1024)), jnp.float32)
    t_q = _time(lambda a: quantize_int8(a)[0], g)
    return (
        "quant_int8_1024x1024", t_q * 1e6,
        f"wire_ratio={(1024*1024 + 1024*4)/(1024*1024*4):.3f}",
    )


def run(smoke: bool = False, out_path: str | None = None):
    """Returns benchmark CSV rows; writes BENCH_kernels.json as a side
    effect (paged bytes-moved model + every row, schema pinned by
    tests/test_bench_kernels.py)."""
    rng = np.random.default_rng(0)
    rows = []
    rows += _flash_rows(rng, smoke)
    paged_rows, paged_record = _paged_rows(rng, smoke)
    rows += paged_rows
    rows += _psgn_rows(rng, smoke)
    rows.append(_quant_row(rng))

    record = {
        "workload": {
            "task": "kernel-lane-microbench", "smoke": smoke,
            "interpret": default_interpret(),
            "backend": jax.default_backend(),
        },
        "paged_decode": paged_record,
        "rows": [
            {"name": n, "us": round(us, 1), "derived": d} for n, us, d in rows
        ],
    }
    path = os.path.abspath(out_path or _DEFAULT_OUT)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    for name, us, derived in run(smoke=args.smoke, out_path=args.out):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
