"""Elastic vs fixed-full-mesh continuous-batching decode throughput, plus
the paged-KV prefix-sharing section, on the 8-device CPU harness.  Writes
``BENCH_serve.json`` at the repo root.

The elastic arms run the SAME ramping arrival trace through the same
ServeEngine / Scheduler; the only difference is the sharding: the fixed arm
pins the full 8-wide data-parallel mesh for every decode step, the elastic
arm lets a ``repro.elastic.MeshLadder`` pick the rung from the live slot
count.  A ramping trace spends most of its steps at low concurrency —
exactly where a full mesh pays collective/dispatch overhead for 1-2 live
slots while the ladder runs them on 1-2 devices.

The ``paged`` section drives a shared-system-prompt ramping trace (every
request opens with the same prefix) through the block-pool engine twice —
prefix sharing on vs off.  Sharing-off re-prefills every prompt in full
(the old dense-cache engine's compute profile); sharing-on computes the
shared prefix blocks EXACTLY ONCE and each request only its divergent tail
(asserted).  The section also records the paged-vs-dense MEMORY footprint:
peak live pool blocks x block size against the dense engine's
``max_slots * max_seq`` preallocation.

The ``policy`` section drives a two-tenant burst trace (a big tenant's
burst up front, a small tenant trickling in just behind it) through one
engine per ``serve.policy`` ServePolicy and scores per-tenant queue wait
(decode steps between submit and admission, p50/p95).  Under FIFO the
small tenant queues behind the entire burst; fair-share deficit
round-robin admits it at the first post-burst boundary — the section
asserts the strict minority-p95 reduction.

Each arm drives the trace twice: pass 1 warms the (bucket, rung) compile
caches, pass 2 is measured (tokens/s excludes compilation, like the other
benches' warmup convention).

  PYTHONPATH=src python -m benchmarks.bench_serve [--smoke] [--policy fair]
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

from repro.utils.xla_env import force_host_device_count

# Effective only before the first jax backend init (a no-op under pytest,
# where conftest already forced 8 devices).
force_host_device_count(8)

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.batch_policy import num_buckets
from repro.dist.plan import ShardingPlan, make_mesh, use_plan
from repro.elastic import MeshLadder
from repro.models import transformer as tf
from repro.serve import POLICIES, Request, ServeEngine, padded_prompt_len

_DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")

MAX_SLOTS = 8


def _cfg():
    return ModelConfig(
        name="bench-serve", family="dense", num_layers=2, d_model=128,
        num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=256,
        pattern=("attn",), param_dtype="float32", compute_dtype="float32",
        xent_chunk=32, remat=False,
    )


def _trace(smoke: bool, seed: int = 0):
    """(arrival_step, Request) pairs: the arrival gap shrinks over the trace
    (the ramp), so concurrency climbs from ~1 toward the full slot count."""
    rng = np.random.default_rng(seed)
    gaps = [12, 12, 12, 12, 8, 8, 8, 8, 6, 6, 4, 4, 2, 2, 1, 1, 0, 0, 0, 0]
    max_new = 16
    if smoke:
        gaps = [8, 8, 6, 6, 4, 2, 0, 0]
        max_new = 8
    trace, step = [], 0
    for gap in gaps:
        trace.append((step, Request(
            prompt=rng.integers(1, 256, size=int(rng.integers(4, 8))).astype(np.int32),
            max_new_tokens=max_new,
        )))
        step += gap
    return trace


def _drive(engine: ServeEngine, trace) -> tuple[list, float]:
    """Submit each request when the engine's decode-step clock reaches its
    arrival step; drain; return (results, wall seconds)."""
    start = engine.stats.steps
    pending = list(trace)
    rids = []
    t0 = time.time()
    while pending or engine.busy:
        while pending and engine.stats.steps - start >= pending[0][0]:
            rids.append(engine.submit(pending.pop(0)[1]))
        if not engine.step() and pending:
            # idle gap in the arrival schedule: jump to the next arrival
            rids.append(engine.submit(pending.pop(0)[1]))
    wall = time.time() - t0
    return [engine.result(rid) for rid in rids], wall


def _serve(mode: str, smoke: bool, policy: str = "fifo"):
    cfg = _cfg()
    params = tf.init_params(cfg, jax.random.key(0))
    devices = jax.devices()
    ladder = None
    ctx = contextlib.nullcontext()
    if mode == "elastic":
        ladder = MeshLadder(devices, granule=1)
    elif mode == "fixed":
        mesh = make_mesh((len(devices),), ("data",))
        ctx = use_plan(ShardingPlan(mesh=mesh, tp=None))
    else:
        raise ValueError(mode)
    with ctx:
        engine = ServeEngine(cfg, params, max_slots=MAX_SLOTS, max_seq=128,
                             elastic=ladder, policy=policy)
        _drive(engine, _trace(smoke))  # pass 1: warm the compile caches
        warm_compiles = engine.stats.compiles
        warm_stats = engine.stats.as_dict()
        results, wall = _drive(engine, _trace(smoke))  # pass 2: measured
    stats = engine.stats
    tokens = sum(r.steps for r in results)
    return {
        "devices": len(devices),
        "policy": policy,
        "tokens": tokens,
        "wall_s": round(wall, 3),
        "tokens_per_sec": round(tokens / wall, 2) if wall > 0 else 0.0,
        "windowed_tokens_per_sec": round(stats.tokens_per_sec, 2),
        "decode_steps": stats.steps - warm_stats["steps"],
        "slot_steps": stats.slot_steps - warm_stats["slot_steps"],
        "compiles": stats.compiles,
        "compiles_in_measured_pass": stats.compiles - warm_compiles,
        "buckets": stats.buckets,
        "rungs": stats.rungs,
        "reshards": stats.reshards,
        "resizes": stats.resizes,
        "ladder_dp": ladder.widths if ladder else None,
        "num_rungs": ladder.num_rungs if ladder else 1,
    }


def _shared_trace(smoke: bool, seed: int = 1):
    """Shared-system-prompt ramp: every prompt = common prefix + distinct
    tail, all the SAME raw length (prompts are left-padded, so equal length
    keeps the padded streams — and their chain hashes — aligned)."""
    rng = np.random.default_rng(seed)
    n, raw, pre = (6, 12, 8) if smoke else (12, 24, 16)
    max_new = 8 if smoke else 16
    prefix = rng.integers(1, 256, size=pre).astype(np.int32)
    trace, step = [], 0
    for _ in range(n):
        tail = rng.integers(1, 256, size=raw - pre).astype(np.int32)
        trace.append((step, Request(prompt=np.concatenate([prefix, tail]),
                                    max_new_tokens=max_new)))
        step += 4  # staggered: the head request's prefill lands first
    return trace, n, raw, pre


def _paged(smoke: bool):
    """The prefix-sharing section: sharing on vs off on the SAME trace and
    engine geometry (both paged; sharing-off's full re-prefill per prompt is
    the old dense engine's compute profile)."""
    cfg = _cfg()
    params = tf.init_params(cfg, jax.random.key(0))
    block = 8
    arms = {}
    for name, sharing in (("shared_prefix", True), ("no_sharing", False)):
        trace, n, raw, pre = _shared_trace(smoke)
        engine = ServeEngine(cfg, params, max_slots=MAX_SLOTS, max_seq=128,
                             prompt_granule=8, block_size=block,
                             prefill_chunk=block, prefix_sharing=sharing)
        _drive(engine, trace)  # pass 1: warm compiles + (if on) the registry
        warm = engine.stats.as_dict()
        results, wall = _drive(engine, _shared_trace(smoke)[0])  # measured
        st = engine.stats
        plen = padded_prompt_len(raw, 8)
        first_chunks = plen // block
        tail_chunks = (plen - ((plen - raw + pre) // block) * block) // block
        if sharing:
            # the acceptance invariant: the shared prefix prefilled ONCE —
            # request 1 in full, every other request only its tail (pass 2
            # replays full-prompt cache hits: zero chunks)
            expect = first_chunks + (n - 1) * tail_chunks
            assert st.prefill_chunks == expect, (st.prefill_chunks, expect)
            assert st.shared_prefill_hits == n  # pass 2: all instant
        else:
            assert st.prefill_chunks == 2 * n * first_chunks
        tokens = sum(r.steps for r in results)
        arms[name] = {
            "tokens": tokens,
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(tokens / wall, 2) if wall > 0 else 0.0,
            "prefill_chunks": st.prefill_chunks,
            "prefill_chunks_measured_pass": st.prefill_chunks
            - warm["prefill_chunks"],
            "shared_prefill_hits": st.shared_prefill_hits,
            "shared_blocks": st.shared_blocks,
            "peak_blocks": st.peak_blocks,
            "compiles_in_measured_pass": st.compiles - warm["compiles"],
        }
        engine.pool.check()
        pool_blocks, cow = engine.pool.num_blocks, st.cow_copies
    dense_tokens = MAX_SLOTS * 128  # the dense layout's per-slot max_seq rows
    peak = max(arms[a]["peak_blocks"] for a in arms)
    ratio = arms["shared_prefix"]["tokens_per_sec"] / max(
        arms["no_sharing"]["tokens_per_sec"], 1e-9)
    return {
        "block_size": block,
        "pool_blocks": pool_blocks,
        "peak_blocks": peak,
        "peak_resident_tokens": peak * block,
        "dense_resident_tokens": dense_tokens,
        "memory_vs_dense": round(peak * block / dense_tokens, 4),
        "cow_copies": cow,
        "shared_prefix": arms["shared_prefix"],
        "no_sharing": arms["no_sharing"],
        "sharing_vs_dense_tokens_per_sec": round(ratio, 3),
    }


def _burst_trace(smoke: bool, seed: int = 2):
    """Two-tenant contention trace: tenant ``big`` bursts every request at
    step 0; tenant ``small`` trickles in one per step just behind it, so the
    burst is already slot-resident when the small tenant queues.  Every
    request generates the same token count — admissions happen in clean
    waves, which makes the per-policy queue waits directly comparable."""
    rng = np.random.default_rng(seed)
    n_big, n_small = (10, 3) if smoke else (16, 4)
    max_new = 8 if smoke else 16

    def _req(tenant: str, priority: int) -> Request:
        return Request(
            prompt=rng.integers(1, 256, size=int(rng.integers(4, 8))).astype(
                np.int32),
            max_new_tokens=max_new, tenant=tenant, priority=priority,
        )

    trace = [(0, _req("big", 0)) for _ in range(n_big)]
    trace += [(1 + i, _req("small", 1)) for i in range(n_small)]
    return trace


def _drive_waits(engine: ServeEngine, trace):
    """Like :func:`_drive` but also scores queue wait per request: decode
    steps between submit and the boundary that admitted it (a rid leaving
    ``Scheduler.queued()`` has been assigned a slot)."""
    start = engine.stats.steps
    pending = list(trace)
    submit_step: dict[int, int] = {}
    admit_step: dict[int, int] = {}
    tenant_of: dict[int, str] = {}
    waiting: set[int] = set()

    def _submit(item):
        rid = engine.submit(item[1])
        submit_step[rid] = engine.stats.steps - start
        tenant_of[rid] = item[1].tenant
        waiting.add(rid)

    def _settle():
        still = {rid for rid, _, _ in engine.sched.queued()}
        for rid in [r for r in waiting if r not in still]:
            admit_step[rid] = engine.stats.steps - start
            waiting.discard(rid)

    while pending or engine.busy:
        while pending and engine.stats.steps - start >= pending[0][0]:
            _submit(pending.pop(0))
        if not engine.step() and pending:
            _submit(pending.pop(0))
        _settle()
    assert not waiting, f"requests never admitted: {sorted(waiting)}"
    waits: dict[str, list[int]] = {}
    for rid, t in submit_step.items():
        waits.setdefault(tenant_of[rid], []).append(admit_step[rid] - t)
    return waits


def _policy(smoke: bool):
    """The policy section: the same burst trace through one engine per
    ServePolicy, scored on per-tenant queue wait.  Slot capacity is held
    below the burst size so admission ORDER is the only thing the policies
    can differ on — tokens decoded are identical across arms."""
    cfg = _cfg()
    params = tf.init_params(cfg, jax.random.key(0))
    slots = 4
    out = {"workload": {"task": "two-tenant-burst", "max_slots": slots,
                        "tenants": ["big", "small"], "smoke": smoke}}
    for name in POLICIES:
        engine = ServeEngine(cfg, params, max_slots=slots, max_seq=64,
                             policy=name)
        waits = _drive_waits(engine, _burst_trace(smoke))
        out[name] = {
            tenant: {
                "n": len(w),
                "p50_wait_steps": round(float(np.percentile(w, 50)), 2),
                "p95_wait_steps": round(float(np.percentile(w, 95)), 2),
                "mean_wait_steps": round(float(np.mean(w)), 2),
            }
            for tenant, w in sorted(waits.items())
        }
    fifo_p95 = out["fifo"]["small"]["p95_wait_steps"]
    fair_p95 = out["fair"]["small"]["p95_wait_steps"]
    # the acceptance invariant: deficit round-robin strictly cuts the
    # minority tenant's tail wait vs queueing behind the whole burst
    assert fair_p95 < fifo_p95, (fair_p95, fifo_p95)
    out["fair_vs_fifo_minority_p95"] = round(fair_p95 / max(fifo_p95, 1e-9), 4)
    return out


def run(smoke: bool = False, out_path: str | None = None, policy: str = "fifo"):
    """Returns benchmark CSV rows; writes the JSON record as a side effect."""
    fixed = _serve("fixed", smoke, policy=policy)
    elastic = _serve("elastic", smoke, policy=policy)
    paged = _paged(smoke)
    pol = _policy(smoke)

    bound = num_buckets(MAX_SLOTS, 1) * elastic["num_rungs"]
    ratio = elastic["tokens_per_sec"] / max(fixed["tokens_per_sec"], 1e-9)
    record = {
        "workload": {"task": "ramping-request-trace", "max_slots": MAX_SLOTS,
                     "max_seq": 128, "smoke": smoke},
        "fixed_full_mesh": fixed,
        "elastic": elastic,
        "paged": paged,
        "policy": pol,
        "elastic_vs_fixed_tokens_per_sec": round(ratio, 3),
        "compile_bound_bucket_x_rung": bound,
    }
    path = os.path.abspath(out_path or _DEFAULT_OUT)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)

    assert elastic["compiles"] <= bound, (elastic, bound)

    rows = []
    for name, r in (("elastic_ladder", elastic), ("fixed_full_mesh", fixed)):
        rows.append((
            f"serve_{name}",
            1e6 / r["tokens_per_sec"] if r["tokens_per_sec"] else 0.0,
            f"tokens_per_sec={r['tokens_per_sec']};compiles={r['compiles']};"
            f"slot_steps={r['slot_steps']}",
        ))
    rows.append((
        "serve_elastic_speedup", 0.0,
        f"elastic_vs_fixed_tokens_per_sec={ratio:.3f};"
        f"reshards={elastic['reshards']};ladder={elastic['ladder_dp']};"
        f"json={os.path.basename(path)}",
    ))
    rows.append((
        "serve_paged_prefix_sharing", 0.0,
        f"sharing_vs_dense_tokens_per_sec={paged['sharing_vs_dense_tokens_per_sec']};"
        f"memory_vs_dense={paged['memory_vs_dense']};"
        f"prefill_chunks={paged['shared_prefix']['prefill_chunks']};"
        f"peak_blocks={paged['peak_blocks']}/{paged['pool_blocks']}",
    ))
    rows.append((
        "serve_policy_fairness", 0.0,
        f"fair_vs_fifo_minority_p95={pol['fair_vs_fifo_minority_p95']};"
        f"fifo_small_p95={pol['fifo']['small']['p95_wait_steps']};"
        f"fair_small_p95={pol['fair']['small']['p95_wait_steps']};"
        f"priority_small_p95={pol['priority']['small']['p95_wait_steps']}",
    ))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--policy", default="fifo", choices=list(POLICIES),
                    help="ServePolicy for the elastic/fixed throughput arms "
                         "(the policy section always sweeps all of them)")
    args = ap.parse_args()
    rows = run(smoke=args.smoke, out_path=args.out, policy=args.policy)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
