"""Fault-tolerance supervisor: runs training with failure injection and
checkpoint/restart, verifying trajectory continuity.

At cluster scale this process would watch worker heartbeats and relaunch the
SPMD job from the latest checkpoint on any failure; here it exercises exactly
that logic in-process (the restart path is identical: fresh Trainer +
``resume()``), plus a step-time watchdog for straggler detection.

  python -m repro.launch.supervisor --epochs 12 --fail-at 4 --fail-at 8

With ``--elastic`` the job runs on a ``repro.elastic`` MeshLadder: a failure
injected after the batch has grown restarts onto a DIFFERENT (wider) rung —
the checkpoint is topology-free and the resumed Trainer re-derives its rung
from the restored batch size.

  python -m repro.launch.supervisor --epochs 6 --fail-at 3 --elastic

A ladder needs several devices; on a CPU host ask for them with
``JAX_PLATFORMS=cpu`` and ``--devices 8``.

With ``--pods N`` the job runs on a ``repro.pod.PodLadder`` (cross-pod rungs
move compressed gradients) and ``--lose-pod EPOCH[:POD]`` injects a HOST
loss: instead of crash + checkpoint restore, the supervisor marks the pod
unhealthy and DEMOTES — the surviving state reshards onto the widest
all-healthy rung and training carries straight on (typed ``pod_lost`` /
``demote`` run-log events record it).

  JAX_PLATFORMS=cpu python -m repro.launch.supervisor --devices 8 \
      --epochs 6 --pods 2 --lose-pod 3
"""

from __future__ import annotations

import argparse
import contextlib
import time

import numpy as np

from repro.utils.logging import get_logger

# NOTE: nothing at module level may *initialize* the jax backend: with
# --devices, main() forces the CPU host-device count via XLA_FLAGS, which
# must be set before the first device use in the process (repro.ckpt is
# imported lazily in run_supervised for the same reason).

log = get_logger("supervisor")


class InjectedFailure(RuntimeError):
    pass


class Watchdog:
    """Step-time z-score straggler detector.

    ``on_flag(step, z)`` (optional) fires on every flag — the supervisor
    wires it to ``Trainer.inject_event('straggler')``, which the
    ``repro.adapt`` program observes as an ``event`` boundary BETWEEN steps:
    an event-responsive policy can resize the batch / evacuate to a
    narrower elastic rung mid-epoch instead of waiting for the epoch end.
    """

    def __init__(self, window: int = 20, z_thresh: float = 4.0, on_flag=None):
        self.times: list[float] = []
        self.window = window
        self.z_thresh = z_thresh
        self.flagged: list[tuple[int, float]] = []
        self.on_flag = on_flag

    def observe(self, step: int, dt: float):
        self.times.append(dt)
        hist = self.times[-self.window :]
        prev = hist[:-1]
        # Degenerate windows: a z-score needs at least 2 prior observations
        # for a spread.  Keep the historical warm-up (first check at the 5th
        # observation) where the window allows it, but small windows
        # (window < 5) now fire too instead of never.
        if len(prev) < max(2, min(4, self.window - 1)):
            return
        mu, sd = float(np.mean(prev)), float(np.std(prev))
        if sd <= 0.0:
            # Constant history: any deviation is infinitely many sigmas out.
            # Floor the spread relative to the mean so equal step times give
            # z = 0 and a genuine spike still flags, while epsilon-level
            # jitter (the old +1e-9 epsilon made ANY 4ns deviation a
            # "straggler") does not.
            sd = max(abs(mu), 1e-9) * 1e-3
        z = (dt - mu) / sd
        if z > self.z_thresh:
            self.flagged.append((step, z))
            log.warning("straggler: step %d took %.3fs (z=%.1f)", step, dt, z)
            if self.on_flag is not None:
                self.on_flag(step, z)


def _normalize_losses(lose_pod) -> list[tuple[int, int | None]]:
    """``lose_pod`` items are epochs or ``(epoch, pod)`` pairs; None pod
    means "the last pod" (resolved against the live topology)."""
    out: list[tuple[int, int | None]] = []
    for item in lose_pod or []:
        if isinstance(item, (tuple, list)):
            e, p = item
            out.append((int(e), int(p)))
        else:
            out.append((int(item), None))
    return out


def run_supervised(make_trainer, total_epochs: int, fail_at: list[int],
                   ckpt_dir: str, max_restarts: int = 10,
                   tracer=None, runlog=None, lose_pod=None) -> list:
    """``make_trainer(ckpt_manager)`` builds a fresh Trainer bound to the
    checkpoint directory. Failures are injected at the given epochs; each
    crash is answered with a rebuild + resume. Returns the final history.

    ``lose_pod`` injects HOST losses (epochs, or ``(epoch, pod)`` pairs) on
    a ``repro.pod.PodLadder`` trainer: instead of the crash/restart path,
    the pod is marked unhealthy and the trainer DEMOTES — the surviving
    state is resharded onto the widest all-healthy rung with no checkpoint
    restore (``pod_lost`` + ``demote`` run-log events mark it).  Losses
    survive process restarts: a rebuilt ladder is re-marked before resume.

    ``tracer``/``runlog`` (repro.obs) are rebound onto every rebuilt Trainer
    and each (re)start is emitted as a typed ``restart`` event — one trace
    and one run log span the whole supervised run, so a cross-rung restart
    is reconstructable from the single file (restarts=0 marks the initial
    start)."""
    from repro.ckpt import CheckpointManager

    restarts = 0
    pending_failures = set(fail_at)
    pending_losses = _normalize_losses(lose_pod)
    lost_pods: set[int] = set()
    while True:
        mgr = CheckpointManager(ckpt_dir, keep=3)
        trainer = make_trainer(mgr)
        if tracer is not None or runlog is not None:
            trainer.bind_obs(tracer=tracer, runlog=runlog)
        health = getattr(getattr(trainer, "elastic", None), "health", None)
        if (pending_losses or lost_pods) and health is None:
            raise ValueError(
                "lose_pod injection needs a trainer on a repro.pod.PodLadder "
                "(it has no pod health registry to mark)"
            )
        # a rebuilt trainer has a fresh ladder: re-mark earlier losses BEFORE
        # resume() so the restored rung is already health-filtered
        for p in lost_pods:
            health.mark_lost(p)
        trainer.resume()
        if health is not None and lost_pods:
            # no-checkpoint start (resume was a no-op) may still sit on an
            # unhealthy initial rung; demote() no-ops when already healthy
            trainer.demote(note="pods lost before restart")
        rung = getattr(trainer, "rung", None)
        if runlog is not None and runlog.enabled:
            runlog.emit("restart", restarts=restarts,
                        epoch=trainer.cursor.epoch,
                        batch_size=trainer.adapt.batch_size,
                        rung=rung.index if rung is not None else None)
        if rung is not None:
            # elastic restart: the checkpoint's batch size picked the rung,
            # which after a mid-run failure is NOT the ladder's first one
            log.info("elastic: %s on rung %d (dp=%d)",
                     "restarted" if restarts else "starting",
                     rung.index, rung.dp)
        # straggler flags feed the trainer's adapt program as mid-epoch events
        watchdog = Watchdog(
            on_flag=lambda step, z: trainer.inject_event("straggler")
        )
        try:
            while trainer.cursor.epoch < total_epochs:
                t0 = time.time()
                ep = trainer.cursor.epoch
                if ep in pending_failures:
                    pending_failures.discard(ep)
                    raise InjectedFailure(f"injected at epoch {ep}")
                for e, p in [lp for lp in pending_losses if lp[0] == ep]:
                    pending_losses.remove((e, p))
                    pod = p if p is not None else health.num_pods - 1
                    cur = trainer.rung
                    src_rung = cur.index if cur is not None else None
                    health.mark_lost(pod)
                    lost_pods.add(pod)
                    if runlog is not None and runlog.enabled:
                        runlog.emit("pod_lost", pod=pod, epoch=ep,
                                    rung=src_rung)
                    ctx = (tracer.span("demote", scope="train", pod=pod,
                                       epoch=ep)
                           if tracer is not None else contextlib.nullcontext())
                    with ctx:
                        src_i, dst_i = trainer.demote(note=f"pod {pod} lost")
                    if runlog is not None and runlog.enabled:
                        runlog.emit("demote", src=src_i, dst=dst_i,
                                    pods=trainer.rung.pods,
                                    dp=trainer.rung.dp, epoch=ep)
                    log.warning(
                        "pod %d lost at epoch %d: DEGRADED rung %s -> %s "
                        "(dp=%d), no restart", pod, ep, src_i, dst_i,
                        trainer.rung.dp)
                trainer.run_epoch()
                trainer.save()
                watchdog.observe(trainer.cursor.epoch, time.time() - t0)
            return trainer.history
        except InjectedFailure as e:
            restarts += 1
            log.warning("FAILURE: %s — restarting (%d/%d)", e, restarts, max_restarts)
            if restarts > max_restarts:
                raise


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=12)
    ap.add_argument("--fail-at", type=int, action="append", default=[])
    ap.add_argument("--ckpt-dir", default="runs/supervised")
    ap.add_argument("--method", default="divebatch")
    ap.add_argument("--elastic", action="store_true",
                    help="run on a repro.elastic MeshLadder: a mid-run "
                         "failure after the batch has grown restarts onto a "
                         "DIFFERENT (wider) rung than the run started on")
    ap.add_argument("--pods", type=int, default=0,
                    help="run on a repro.pod.PodLadder spanning N virtual "
                         "pods (cross-pod rungs move compressed gradients); "
                         "implies 8 CPU host devices unless --devices")
    ap.add_argument("--lose-pod", action="append", default=[],
                    metavar="EPOCH[:POD]",
                    help="inject a HOST loss at EPOCH (of pod POD, default "
                         "the last pod): the supervisor DEGRADES onto the "
                         "widest all-healthy rung instead of restarting; "
                         "repeatable")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N CPU host devices (before first jax use); "
                         "pair with JAX_PLATFORMS=cpu for the CPU harness")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="record a Chrome/Perfetto trace (repro.obs) spanning "
                         "every restart; writes DIR/trace.json at exit")
    ap.add_argument("--runlog", default=None, nargs="?", const="",
                    metavar="PATH",
                    help="write the schema-versioned JSONL run log, restarts "
                         "included; bare --runlog means <--trace "
                         "DIR>/runlog.jsonl")
    args = ap.parse_args()

    from repro.utils.xla_env import enable_compile_cache, force_host_device_count

    if args.devices:
        # effective until the first backend init (first device use), which in
        # this process is the trainer build below
        force_host_device_count(args.devices)

    import jax

    enable_compile_cache()

    from repro.adapt import (
        AdaBatchPolicy,
        AdaptationProgram,
        DiveBatchPolicy,
        FixedPolicy,
    )
    from repro.data import sigmoid_synthetic
    from repro.elastic import MeshLadder
    from repro.models import small
    from repro.optim import sgd
    from repro.train.loop import ModelFns, Trainer

    train, val, _ = sigmoid_synthetic(n=4000, d=64, seed=0)

    def make_policy_obj():
        # DiveBatch with on_event=True: a Watchdog straggler flag re-fires
        # the (memoryless) rule between steps on the running estimate —
        # the event wiring is live, not just plumbed
        if args.method == "divebatch":
            return DiveBatchPolicy(64, 1024, delta=0.1, dataset_size=len(train),
                                   granule=16, on_event=True)
        if args.method == "adabatch":
            return AdaBatchPolicy(64, 1024, granule=16)
        return FixedPolicy(64, 1024, granule=16)

    def make_ladder():
        if args.pods:
            from repro.pod import PodLadder

            return PodLadder(pods=args.pods, granule=16)
        return MeshLadder(granule=16) if args.elastic else None

    def make_trainer(mgr):
        fns = ModelFns(
            batch_loss=small.logreg_batch_loss,
            example_loss=small.logreg_loss,
            metrics=lambda p, b: {"acc": small.logreg_accuracy(p, b)},
        )
        program = AdaptationProgram(make_policy_obj(), base_lr=1.0,
                                    estimator="exact")
        return Trainer(
            fns, small.logreg_init(jax.random.key(0), 64), sgd(momentum=0.9),
            program, train, val, estimator="exact", ckpt=mgr,
            elastic=make_ladder(),
        )

    from repro.obs import from_cli as obs_from_cli

    lose_pod: list = []
    for spec in args.lose_pod:
        e, _, p = str(spec).partition(":")
        lose_pod.append((int(e), int(p)) if p else int(e))

    tracer, runlog = obs_from_cli(
        args.trace, args.runlog,
        meta={"cmd": "supervisor", "method": args.method,
              "elastic": bool(args.elastic), "fail_at": args.fail_at,
              "pods": args.pods, "lose_pod": args.lose_pod},
    )
    history = run_supervised(make_trainer, args.epochs, args.fail_at,
                             args.ckpt_dir, tracer=tracer, runlog=runlog,
                             lose_pod=lose_pod)
    if tracer is not None:
        print(f"trace: {tracer.save(args.trace)}")
    if runlog is not None:
        runlog.close()
        print(f"runlog: {runlog.path}")
    print(f"completed {len(history)} epochs across restarts; "
          f"final val acc {history[-1].val_metrics.get('acc'):.4f}")


if __name__ == "__main__":
    main()
