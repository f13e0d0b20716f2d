"""End-to-end LM training driver: the production train step (microbatch
accumulation + moment-estimator adaptation) on a transformer LM.

Adaptation runs through ``repro.adapt`` at STEP granularity — the streaming
regime the old epoch-only controller could not express: a tick-fired policy
(DiveBatch over the accumulation window, or ``--method gns`` for the
gradient-noise-scale family) observes the in-jit accumulators every
``--epoch-steps`` optimizer steps via ``read_signals`` (one stacked scalar
transfer) and resizes onto the ``num_micro`` bucket lattice.

Default is a CPU-friendly ~20M-param model for a quick demo; --model-100m
selects the ~100M configuration (same code path; a few hundred steps of it
is the intended single-host run, several minutes/step on CPU — on TPU this
is the config the dry-run lowers for 256 chips).

  PYTHONPATH=src python examples/train_lm.py --steps 30
  PYTHONPATH=src python examples/train_lm.py --method gns --steps 30
  PYTHONPATH=src python examples/train_lm.py --model-100m --steps 300
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.adapt import (
    AdaptationProgram,
    Clock,
    DiveBatchPolicy,
    GradNoisePolicy,
    read_signals,
)
from repro.configs.base import ModelConfig
from repro.data import TokenStream
from repro.models import transformer as tf
from repro.optim import sgd
from repro.train import StepEngine, init_state
from repro.ckpt import CheckpointManager
from repro.utils.xla_env import enable_compile_cache


def model_config(big: bool) -> ModelConfig:
    if big:  # ~100M params
        return ModelConfig(
            name="lm-100m", family="dense", num_layers=12, d_model=768,
            num_heads=12, num_kv_heads=4, d_ff=2048, vocab_size=32_000,
            param_dtype="float32", compute_dtype="float32", xent_chunk=128,
            remat=False,
        )
    return ModelConfig(  # ~20M params
        name="lm-20m", family="dense", num_layers=6, d_model=384,
        num_heads=6, num_kv_heads=2, d_ff=1024, vocab_size=8_000,
        param_dtype="float32", compute_dtype="float32", xent_chunk=128,
        remat=False,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--method", default="divebatch", choices=["divebatch", "gns"])
    ap.add_argument("--model-100m", action="store_true")
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--micro-batch", type=int, default=4)
    ap.add_argument("--m0", type=int, default=8, help="initial global batch (sequences)")
    ap.add_argument("--m-max", type=int, default=64)
    ap.add_argument("--delta", type=float, default=0.5,
                    help="DiveBatch scale: m = delta * n_epoch * Delta_hat")
    ap.add_argument("--epoch-steps", type=int, default=10,
                    help="steps per 'epoch' (diversity/batch-size update period)")
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = model_config(args.model_100m)
    params = tf.init_params(cfg, jax.random.key(0))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"model: {cfg.name}, {n_params/1e6:.1f}M params")

    opt = sgd(momentum=0.9)
    state = init_state(params, opt)
    stream = TokenStream(cfg.vocab_size, seed=0)
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    # one compiled, donated step per num_micro bucket — the same StepEngine
    # the Trainer and the multi-pod dry-run drive
    engine = StepEngine.for_lm(cfg, opt, micro_batch=args.micro_batch)

    # A tick-fired repro.adapt program over the step stream: DiveBatch
    # scaled by the accumulation window (dataset_size=None -> the samples
    # actually seen since the last reset), or the gradient-noise family.
    if args.method == "gns":
        policy = GradNoisePolicy(args.m0, args.m_max, granule=args.micro_batch,
                                 alpha=1.0, on_tick=True)
    else:
        policy = DiveBatchPolicy(args.m0, args.m_max, delta=args.delta,
                                 dataset_size=None, granule=args.micro_batch,
                                 on_tick=True)
    program = AdaptationProgram(policy, base_lr=args.lr, estimator="moment",
                                tick_every=args.epoch_steps)

    m = program.batch_size
    for step in range(args.steps):
        batch_np = stream.batch(step, m, args.seq_len)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        t0 = time.time()
        state, metrics = engine.step(state, batch, program.lr)
        dt = time.time() - t0
        if (step + 1) % program.tick_every == 0:
            # one stacked scalar transfer: diversity + GNS + window samples;
            # the reset starts the next accumulation window
            sig, state = read_signals(state, "moment", reset=True,
                                      batch_size=m,
                                      loss=float(metrics["loss"]))
            program.observe(sig, Clock(epoch=step // program.tick_every,
                                       step=step + 1, boundary="tick"))
            print(f"step {step+1:4d} loss={float(metrics['loss']):.4f} "
                  f"dt={dt:.2f}s  Delta={sig.diversity:.4f} gns={sig.gns:.1f} "
                  f"-> batch {m} -> {program.batch_size}")
            m = program.batch_size
            if mgr:
                mgr.save(step + 1, {"state": state},
                         extra={"program": program.state_dict()})
        elif step % 5 == 0:
            print(f"step {step+1:4d} loss={float(metrics['loss']):.4f} dt={dt:.2f}s batch={m}")

    stats = engine.stats
    print(f"done. compiled buckets: {sorted(stats.buckets)} (num_micro values), "
          f"{stats.compiles} compiles / {stats.steps} steps, donated={stats.donate}")


if __name__ == "__main__":
    main()
