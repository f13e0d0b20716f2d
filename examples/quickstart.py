"""Quickstart: DiveBatch end to end in ~1 minute on CPU.

Trains the paper's synthetic logistic-regression task with a ``repro.adapt``
program (the composable, signal-driven adaptation API), shows the
batch-size/diversity trajectory, checkpoints, kills the trainer, and
resumes — the five core APIs in one file — with ``repro.obs`` telemetry on
the whole way: one span trace (Perfetto-loadable ``trace.json``) and one
typed JSONL run log span both trainers, and ``launch/monitor.py`` prints
the reconstructed schedule at the end.

The adaptation program replaces the old ``AdaptiveBatchController``, which
survives only as a deprecated shim over exactly this object: policies
observe ``Signals`` at ``Clock`` boundaries (epoch ends, every-k-steps
ticks, injected events), and a typed ``LrCoupling`` replaces the string
``lr_rule``/``lr_schedule`` pair.

  PYTHONPATH=src python examples/quickstart.py
"""

import tempfile

import jax

from repro.adapt import AdaptationProgram, DiveBatchPolicy, LrCoupling
from repro.ckpt import CheckpointManager
from repro.core import step_decay
from repro.data import sigmoid_synthetic
from repro.launch import monitor
from repro.models import small
from repro.obs import RunLog, Tracer
from repro.optim import sgd
from repro.train.loop import ModelFns, Trainer
from repro.utils.xla_env import enable_compile_cache


def make_program():
    """DiveBatch: m <- min(m_max, delta * n * Delta_hat), epoch cadence,
    with the paper's background step decay on the learning rate."""
    return AdaptationProgram(
        DiveBatchPolicy(m0=64, m_max=2048, delta=1.0, dataset_size=8000,
                        granule=16),
        base_lr=2.0,
        coupling=LrCoupling(rule="none",              # paper's main setting
                            decay=step_decay(0.75, 20)),  # background decay
        estimator="exact",
    )


def main():
    enable_compile_cache()
    # 1. data + model (the paper's eq. 3 synthetic task)
    train, val, _ = sigmoid_synthetic(n=8000, d=128, seed=0)
    params = small.logreg_init(jax.random.key(0), 128)
    fns = ModelFns(
        batch_loss=small.logreg_batch_loss,
        example_loss=small.logreg_loss,  # per-sample: enables the exact tier
        metrics=lambda p, b: {"acc": small.logreg_accuracy(p, b)},
    )

    # 2. the adaptation program (see make_program above)
    program = make_program()

    # 3. telemetry: one tracer + one run log span the whole session
    #    (equivalently: launch/train.py --trace DIR --runlog)
    run_dir = tempfile.mkdtemp(prefix="divebatch_quickstart_run_")
    tracer = Tracer()
    runlog = RunLog(run_dir, meta={"cmd": "quickstart"})

    # 4. train with checkpointing
    ckpt_dir = tempfile.mkdtemp(prefix="divebatch_quickstart_")
    trainer = Trainer(fns, params, sgd(momentum=0.9), program, train, val,
                      estimator="exact", ckpt=CheckpointManager(ckpt_dir),
                      ckpt_every=2, tracer=tracer, runlog=runlog)
    print("== training 6 epochs ==")
    trainer.run(6)

    # 5. simulate a crash: rebuild everything, resume from the checkpoint
    #    (checkpoints carry the program state — schema v2; pre-redesign v1
    #    controller checkpoints restore through the same path).  The same
    #    obs sinks carry over, so one trace/log covers both trainers.
    print("== 'crash' -> resume ==")
    trainer2 = Trainer(fns, small.logreg_init(jax.random.key(0), 128),
                       sgd(momentum=0.9), make_program(), train, val,
                       estimator="exact", ckpt=CheckpointManager(ckpt_dir),
                       tracer=tracer, runlog=runlog)
    trainer2.resume()
    trainer2.run(2)

    print("\nbatch-size trajectory:",
          [h.batch_size for h in trainer2.history])
    print("diversity trajectory:  ",
          [f"{h.diversity:.3f}" if h.diversity is not None else "-"
           for h in trainer2.history])
    print("final val acc:", trainer2.history[-1].val_metrics["acc"])
    stats = trainer2.engine.stats  # the bucketed compile cache at work
    print(f"engine: {stats.compiles} step compiles for buckets {stats.buckets}, "
          f"{stats.bucket_hits} cache hits, donated={stats.donate}")

    # 6. what the run log + trace captured (launch/monitor.py is the reader:
    #    python -m repro.launch.monitor <run_dir> [--follow] [--trace OUT])
    print("\n== telemetry (repro.obs) ==")
    print("trace:", tracer.save(run_dir), f"({len(tracer.events)} events —"
          " load it in Perfetto / chrome://tracing)")
    runlog.close()
    print(monitor.summary(monitor.load(run_dir)))


if __name__ == "__main__":
    main()
