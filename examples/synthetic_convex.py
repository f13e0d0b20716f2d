"""Paper §5.1 reproduction: synthetic convex + nonconvex experiments.

Runs SGD / DiveBatch / Oracle on the eq. 3 dataset with the paper's protocol
(grid-selected small-batch baseline LR, delta search values, step decay) and
writes a JSON + printed table mirroring Figures 1-2.

  PYTHONPATH=src python examples/synthetic_convex.py [--full]
"""

import argparse
import dataclasses
import json

import jax
import numpy as np

from repro.core import AdaptiveBatchController, make_policy, step_decay
from repro.data import sigmoid_synthetic
from repro.models import small
from repro.optim import sgd
from repro.train.loop import ModelFns, Trainer
from repro.utils.xla_env import enable_compile_cache


def run_method(task, method, estimator, *, n, d, epochs, delta, m0, m_max, lr, seed):
    train, val, _ = sigmoid_synthetic(n=n, d=d, seed=seed)
    if task == "convex":
        params = small.logreg_init(jax.random.key(seed), d)
        fns = ModelFns(small.logreg_batch_loss, small.logreg_loss,
                       lambda p, b: {"acc": small.logreg_accuracy(p, b)})
    else:
        params = small.mlp_init(jax.random.key(seed), d)
        fns = ModelFns(small.mlp_batch_loss, small.mlp_loss,
                       lambda p, b: {"acc": small.mlp_accuracy(p, b)})
    ctrl = AdaptiveBatchController(
        make_policy(method if method != "oracle" else "divebatch",
                    m0=m0, m_max=m_max, delta=delta, dataset_size=len(train),
                    granule=16),
        base_lr=lr, lr_schedule=step_decay(0.75, 20),
    )
    t = Trainer(fns, params, sgd(momentum=0.9), ctrl, train, val,
                estimator=estimator, seed=seed)
    return t.run(epochs, verbose=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale n=20000, d=512, 100 epochs (slow on CPU)")
    ap.add_argument("--out", default="runs/synthetic_convex.json")
    args = ap.parse_args()
    enable_compile_cache()

    scale = dict(n=20_000, d=512, epochs=100) if args.full else dict(n=4000, d=128, epochs=15)
    results = {}
    for task, delta, lr in [("convex", 1.0, 2.0), ("nonconvex", 0.1, 0.5)]:
        for method, est in [("sgd", "none"), ("divebatch", "exact"), ("oracle", "oracle")]:
            hist = run_method(task, method, est, delta=delta, m0=64,
                              m_max=1024 if not args.full else 4096,
                              lr=lr, seed=0, **scale)
            key = f"{task}/{method}"
            results[key] = [dataclasses.asdict(h) for h in hist]
            accs = [h.val_metrics["acc"] for h in hist]
            print(f"{key:24s} final_acc={accs[-1]:.4f} "
                  f"end_batch={hist[-1].batch_size:5d} "
                  f"acc_curve={[round(a, 3) for a in accs[:: max(len(accs)//6, 1)]]}")

    import os

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote", args.out)


if __name__ == "__main__":
    main()
