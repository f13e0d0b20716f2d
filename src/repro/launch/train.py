"""Training CLI: paper reproductions and LM training with adaptive batching.

Adaptation is built on ``repro.adapt``: ``--method`` picks the policy
(divebatch / adabatch / sgd / oracle / gns — the gradient-noise-scale
family), ``--tick-every N`` enables mid-epoch decisions every N steps (with
``--elastic`` a mid-epoch resize also reshards the rung between steps), and
``--hysteresis B`` wraps the policy in a tolerance band around the pow2
bucket thresholds.

Examples:
  python -m repro.launch.train --task synthetic-convex --method divebatch
  python -m repro.launch.train --task imagelike --method adabatch --epochs 30
  python -m repro.launch.train --task synthetic-convex --method gns \
      --tick-every 8 --elastic
"""

from __future__ import annotations

import argparse
import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.adapt import (
    AdaBatchPolicy,
    AdaptationProgram,
    DiveBatchPolicy,
    FixedPolicy,
    GradNoisePolicy,
    Hysteresis,
    LrCoupling,
)
from repro.core import step_decay
from repro.data import imagelike_classification, sigmoid_synthetic
from repro.dist.plan import ShardingPlan, make_mesh, use_plan
from repro.elastic import MeshLadder
from repro.obs import from_cli as obs_from_cli
from repro.optim import sgd
from repro.train.loop import ModelFns, Trainer
from repro.ckpt import CheckpointManager
from repro.utils.xla_env import enable_compile_cache


def build_task(task: str, seed: int):
    from repro.models import resnet, small

    if task == "synthetic-convex":
        train, val, _ = sigmoid_synthetic(n=20_000, d=512, seed=seed)
        params = small.logreg_init(jax.random.key(seed), 512)
        fns = ModelFns(
            batch_loss=small.logreg_batch_loss,
            example_loss=small.logreg_loss,
            metrics=lambda p, b: {"acc": small.logreg_accuracy(p, b)},
        )
        return fns, params, train, val
    if task == "synthetic-nonconvex":
        train, val, _ = sigmoid_synthetic(n=20_000, d=512, seed=seed)
        params = small.mlp_init(jax.random.key(seed), 512)
        fns = ModelFns(
            batch_loss=small.mlp_batch_loss,
            example_loss=small.mlp_loss,
            metrics=lambda p, b: {"acc": small.mlp_accuracy(p, b)},
            probe_loss=small.mlp_batch_loss_with_probes,
            probe_specs=small.mlp_probe_specs,
        )
        return fns, params, train, val
    if task == "imagelike":
        train, val = imagelike_classification(n=6_000, hw=16, num_classes=10, seed=seed)
        params = resnet.resnet_init(jax.random.key(seed), depth=8, width=8)
        fns = ModelFns(
            batch_loss=resnet.resnet_batch_loss,
            example_loss=resnet.resnet_loss,
            metrics=lambda p, b: {"acc": resnet.resnet_accuracy(p, b)},
        )
        return fns, params, train, val
    raise ValueError(f"unknown task {task!r}")


def make_program(args, dataset_size: int) -> AdaptationProgram:
    """Build the repro.adapt program for the CLI flags (the single
    adaptation path — the legacy AdaptiveBatchController is a shim over
    exactly this object)."""
    common = dict(m0=args.batch_size, m_max=args.max_batch_size,
                  granule=args.granule)
    tick = args.tick_every > 0
    if args.method in ("sgd", "fixed"):
        policy = FixedPolicy(**common)
    elif args.method == "adabatch":
        policy = AdaBatchPolicy(resize_freq=args.resize_freq, **common)
    elif args.method in ("divebatch", "oracle"):
        policy = DiveBatchPolicy(
            delta=args.delta, dataset_size=dataset_size,
            oracle=args.method == "oracle", on_tick=tick, **common,
        )
    elif args.method == "gns":
        policy = GradNoisePolicy(alpha=args.gns_alpha, on_tick=tick, **common)
    else:
        raise ValueError(f"unknown method {args.method!r}")
    if args.hysteresis > 0:
        policy = Hysteresis(policy, band=args.hysteresis)
    decay = step_decay(args.lr_decay, args.lr_decay_every) if args.lr_decay < 1 else None
    return AdaptationProgram(
        policy,
        base_lr=args.lr,
        coupling=LrCoupling(rule=args.lr_rule, decay=decay),
        estimator=args.estimator,
        tick_every=args.tick_every,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="synthetic-convex")
    ap.add_argument("--method", default="divebatch",
                    choices=["sgd", "adabatch", "divebatch", "oracle", "gns"])
    ap.add_argument("--estimator", default="exact",
                    choices=["exact", "gram", "moment", "oracle"])
    ap.add_argument("--tick-every", type=int, default=0,
                    help="mid-epoch adaptation: observe the running signals "
                         "every N optimizer steps (0 = epoch boundaries "
                         "only); a mid-epoch decision resizes the batch and "
                         "reshards the elastic rung between steps")
    ap.add_argument("--gns-alpha", type=float, default=1.0,
                    help="--method gns: target batch = alpha * measured "
                         "gradient-noise scale")
    ap.add_argument("--hysteresis", type=float, default=0.0,
                    help="tolerance band around pow2 bucket thresholds "
                         "(e.g. 0.1): resizes within the band hold the "
                         "current size, making the schedule rung-invariant "
                         "under dp-reduction-order jitter")
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--max-batch-size", type=int, default=2048)
    ap.add_argument("--delta", type=float, default=0.1)
    ap.add_argument("--granule", type=int, default=16)
    ap.add_argument("--resize-freq", type=int, default=20)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--lr-rule", default="none", choices=["none", "linear", "sqrt"])
    ap.add_argument("--lr-decay", type=float, default=0.75)
    ap.add_argument("--lr-decay-every", type=int, default=20)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dp", type=int, default=0,
                    help="data-parallel shards; >0 activates a dist plan over "
                         "that many local devices (same engine code path as "
                         "the multi-pod dry-run)")
    ap.add_argument("--elastic", action="store_true",
                    help="co-adapt the device footprint with the batch size: "
                         "a repro.elastic MeshLadder over --dp (default: all) "
                         "local devices, rung transitions at the epoch "
                         "boundaries that resize the batch")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable TrainState buffer donation (debugging)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--out", default=None,
                    help="write run JSON here: {'history': [epoch records], "
                         "'engine': EngineStats}")
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

    if args.method == "oracle":
        args.estimator = "oracle"

    # The CPU-test and multi-pod paths are the same engine: with --dp the
    # whole run executes under a ShardingPlan (batches dp-sharded, GSPMD
    # propagates into the donated step); without one, constrain() is a no-op
    # and the identical code runs single-device. --elastic replaces the fixed
    # plan with a MeshLadder: the batch-size signal drives the sharding plan,
    # not just the step bucket.
    plan_ctx = contextlib.nullcontext()
    ladder = None
    if args.elastic:
        ndev = args.dp or len(jax.devices())
        if ndev > len(jax.devices()):
            raise SystemExit(
                f"--dp {ndev} exceeds the {len(jax.devices())} available "
                f"devices (the fixed --dp path would fail the same way)"
            )
        ladder = MeshLadder(jax.devices()[:ndev], granule=args.granule)
    elif args.dp:
        mesh = make_mesh((args.dp,), ("data",))
        plan_ctx = use_plan(ShardingPlan(mesh=mesh))

    tracer, runlog = obs_from_cli(
        args.trace, args.runlog,
        meta={"cmd": "train", "task": args.task, "method": args.method,
              "estimator": args.estimator, "seed": args.seed,
              "elastic": bool(args.elastic)},
    )
    with plan_ctx:
        fns, params, train, val = build_task(args.task, args.seed)
        program = make_program(args, len(train))
        trainer = Trainer(
            fns, params, sgd(momentum=args.momentum, weight_decay=args.weight_decay),
            program, train, val,
            estimator=args.estimator if args.method in ("divebatch", "oracle", "gns") else "none",
            seed=args.seed,
            ckpt=CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None,
            ckpt_every=args.ckpt_every,
            donate=not args.no_donate,
            elastic=ladder,
            tracer=tracer,
            runlog=runlog,
        )
        if args.resume and trainer.ckpt:
            trainer.resume()
        remaining = args.epochs - trainer.cursor.epoch
        history = trainer.run(max(remaining, 0))
    if tracer is not None:
        print(f"trace: {tracer.save(args.trace)}")
    if runlog is not None:
        runlog.close()
        print(f"runlog: {runlog.path}")
    stats = trainer.engine.stats
    if args.out:
        import dataclasses

        with open(args.out, "w") as f:
            json.dump(
                {"history": [dataclasses.asdict(r) for r in history],
                 "engine": stats.as_dict()},
                f, indent=1,
            )
    final = history[-1] if history else None
    if final:
        print(f"final: epoch={final.epoch} val_loss={final.val_loss:.4f} "
              f"metrics={final.val_metrics} batch={final.batch_size}")
    print(f"engine: compiles={stats.compiles} (bound {program.compile_bound}) "
          f"hits={stats.bucket_hits} buckets={stats.buckets} "
          f"dispatch-steps/s={stats.dispatch_steps_per_sec:.1f} donated={stats.donate}")
    mid = [a for a in program.history if a.boundary != "epoch"]
    if mid:
        print(f"adapt: {len(mid)} mid-epoch decisions "
              f"({sum(a.rescaled for a in mid)} resized) via "
              f"{sorted(set(a.boundary for a in mid))}")
    if ladder is not None:
        print(f"elastic: ladder dp={ladder.widths} reshards={stats.reshards} "
              f"rungs-per-compile={stats.rungs}")


if __name__ == "__main__":
    main()
