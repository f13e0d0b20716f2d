"""Serving CLI: elastic continuous-batching decode over the model zoo.

The serving counterpart of ``launch/train.py``: ``--elastic`` wires a
``repro.elastic.MeshLadder`` into the ``ServeEngine`` so the live decode
batch drives the device footprint (rung transitions reshard the params and
the KV cache between steps); ``--dp N`` instead pins a fixed N-wide
data-parallel plan for the whole run (today's behaviour, the baseline
``benchmarks/bench_serve.py`` measures against).

``--policy fifo|priority|fair`` selects the ``serve.policy.ServePolicy``
driving admission order / slot budget at every boundary (fifo is the
default and reproduces the pre-hook engine; priority/fair read the
``tenant``/``priority`` metadata ``--tenants`` stamps onto the synthetic
requests).

Examples:
  python -m repro.launch.serve --arch yi-6b --requests 16
  python -m repro.launch.serve --elastic --requests 32 --ramp 8
  python -m repro.launch.serve --policy fair --tenants 2 --ramp 2
  python -m repro.launch.serve --dp 8 --sampler categorical --out serve.json
"""

from __future__ import annotations

import argparse
import contextlib
import json

import jax
import numpy as np

from repro.configs import get_config
from repro.dist.plan import ShardingPlan, make_mesh, use_plan
from repro.elastic import MeshLadder
from repro.models import transformer as tf
from repro.obs import from_cli as obs_from_cli
from repro.serve import POLICIES, Request, ServeEngine
from repro.utils.xla_env import enable_compile_cache


def build_requests(cfg, n: int, *, max_new: int, seed: int,
                   tenants: int = 0) -> list[Request]:
    """Synthetic request set; with ``tenants > 0`` request *i* belongs to
    tenant ``t<i % tenants>`` with priority ``i % tenants`` (so priority
    and fair-share policies have classes to act on)."""
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt=rng.integers(
                1, cfg.vocab_size, size=int(rng.integers(4, 24))
            ).astype(np.int32),
            max_new_tokens=int(rng.integers(max(max_new // 2, 1), max_new + 1)),
            tenant=f"t{i % tenants}" if tenants else None,
            priority=i % tenants if tenants else 0,
        )
        for i in range(n)
    ]


def serve_trace(engine: ServeEngine, requests: list[Request], ramp: int) -> list:
    """Drive an arrival trace: one request every ``ramp`` engine steps
    (``ramp=0`` submits everything up front), then drain."""
    rids = []
    if ramp <= 0:
        rids = [engine.submit(r) for r in requests]
        engine.drain()
    else:
        pending = list(requests)
        while pending or engine.busy:
            if pending:
                rids.append(engine.submit(pending.pop(0)))
                for _ in range(ramp):
                    if not engine.step():
                        break
            else:
                engine.step()
    return [engine.result(rid) for rid in rids]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b",
                    help="configs registry arch (served reduced + shrunk "
                         "unless --full-size)")
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--ramp", type=int, default=0,
                    help="submit one request every N engine steps (0 = all "
                         "up front) — a ramping trace is where the elastic "
                         "ladder pays")
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--prompt-granule", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=0,
                    help="KV pool block size in tokens (0 = prompt granule)")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="KV pool capacity in blocks (0 = worst-case for "
                         "max_slots, pow2)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prefill chunk length in tokens (0 = whole prompt "
                         "per boundary); chunks interleave with decode")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable chain-hash prompt prefix sharing")
    ap.add_argument("--policy", default="fifo", choices=list(POLICIES),
                    help="serve-side admission policy (serve/policy.py); "
                         "fifo reproduces the pre-hook engine token-for-token")
    ap.add_argument("--tenants", type=int, default=0,
                    help="stamp round-robin tenant/priority metadata onto the "
                         "synthetic requests (gives --policy priority|fair "
                         "classes to act on)")
    ap.add_argument("--sampler", default="greedy", choices=["greedy", "categorical"])
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dp", type=int, default=0,
                    help="pin a fixed dp-wide plan (the non-elastic baseline)")
    ap.add_argument("--elastic", action="store_true",
                    help="MeshLadder over --dp (default: all) local devices; "
                         "the live slot count picks the rung")
    ap.add_argument("--out", default=None, help="write {results, stats} JSON")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="record a Chrome/Perfetto trace (repro.obs) and "
                         "write DIR/trace.json at exit")
    ap.add_argument("--runlog", default=None, nargs="?", const="",
                    metavar="PATH",
                    help="write the schema-versioned JSONL run log "
                         "(repro.obs.runlog; read it with launch/monitor.py); "
                         "bare --runlog means <--trace DIR>/runlog.jsonl")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=not args.full_size)
    if not args.full_size:
        cfg = cfg.replace(num_layers=min(cfg.num_layers, 4), d_model=128,
                          num_heads=4, num_kv_heads=2)
    params = tf.init_params(cfg, jax.random.key(args.seed))

    plan_ctx = contextlib.nullcontext()
    ladder = None
    if args.elastic:
        ndev = args.dp or len(jax.devices())
        if ndev > len(jax.devices()):
            raise SystemExit(
                f"--dp {ndev} exceeds the {len(jax.devices())} available devices"
            )
        ladder = MeshLadder(jax.devices()[:ndev], granule=1)
    elif args.dp:
        mesh = make_mesh((args.dp,), ("data",))
        plan_ctx = use_plan(ShardingPlan(mesh=mesh, tp=None))

    tracer, runlog = obs_from_cli(
        args.trace, args.runlog,
        meta={"cmd": "serve", "arch": args.arch, "requests": args.requests,
              "seed": args.seed, "elastic": bool(args.elastic),
              "policy": args.policy},
    )
    with plan_ctx:
        engine = ServeEngine(
            cfg, params, max_slots=args.max_slots, max_seq=args.max_seq,
            sampler=args.sampler, temperature=args.temperature,
            seed=args.seed, prompt_granule=args.prompt_granule,
            elastic=ladder,
            block_size=args.block_size or None,
            pool_blocks=args.pool_blocks or None,
            prefill_chunk=args.prefill_chunk,
            prefix_sharing=not args.no_prefix_sharing,
            policy=args.policy,
            tracer=tracer,
            runlog=runlog,
        )
        requests = build_requests(cfg, args.requests,
                                  max_new=args.max_new, seed=args.seed,
                                  tenants=args.tenants)
        results = serve_trace(engine, requests, args.ramp)
    if tracer is not None:
        print(f"trace: {tracer.save(args.trace)}")
    if runlog is not None:
        runlog.close()
        print(f"runlog: {runlog.path}")

    stats = engine.stats
    total = sum(r.steps for r in results)
    print(f"policy: {args.policy}"
          + (f" ({args.tenants} tenants)" if args.tenants else ""))
    print(f"served {len(results)} requests, {total} tokens "
          f"({stats.tokens_per_sec:.1f} tok/s windowed, "
          f"{stats.steps} decode steps, {stats.slot_steps} decoded lanes)")
    print(f"engine: compiles={stats.compiles} (buckets={stats.buckets} "
          f"rungs={stats.rungs}) prefill={stats.prefill_compiles} "
          f"aux={stats.aux_compiles} hits={stats.bucket_hits}")
    print(f"pool: {stats.peak_blocks}/{stats.pool_blocks} blocks peak "
          f"(block={stats.block_size}) chunks={stats.prefill_chunks} "
          f"shared_hits={stats.shared_prefill_hits} "
          f"shared_blocks={stats.shared_blocks} cow={stats.cow_copies}")
    if ladder is not None:
        print(f"elastic: ladder dp={ladder.widths} reshards={stats.reshards} "
              f"resizes={stats.resizes}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(
                {"results": [{"steps": r.steps, "tokens": r.tokens.tolist()}
                             for r in results],
                 "stats": stats.as_dict()},
                f, indent=1,
            )


if __name__ == "__main__":
    main()
