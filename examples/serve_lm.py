"""Serving example: elastic continuous batching through the ServeEngine.

Requests stream in, the Scheduler admits them into pow2 slot buckets,
retires each one at its own EOS/max-token step, and (with more than one
device) a MeshLadder widens/narrows the mesh with the live batch.

``--policy`` swaps the admission policy (serve/policy.py): ``fifo`` is the
default engine behaviour, ``priority``/``fair`` read the tenant/priority
metadata this example stamps onto every other request.

  PYTHONPATH=src python examples/serve_lm.py
  PYTHONPATH=src python examples/serve_lm.py --policy fair
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.elastic import MeshLadder
from repro.models import transformer as tf
from repro.serve import POLICIES, Request, ServeEngine
from repro.utils.xla_env import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="fifo", choices=list(POLICIES))
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config("yi-6b", reduced=True).replace(num_layers=4, d_model=128,
                                                    num_heads=4, num_kv_heads=2)
    params = tf.init_params(cfg, jax.random.key(0))
    ladder = MeshLadder(granule=1) if len(jax.devices()) > 1 else None
    engine = ServeEngine(cfg, params, max_slots=4, max_seq=256, elastic=ladder,
                         policy=args.policy)

    rng = np.random.default_rng(0)
    requests = [
        Request(prompt=rng.integers(1, cfg.vocab_size, size=rng.integers(4, 24)).astype(np.int32),
                max_new_tokens=16,
                tenant=f"t{i % 2}", priority=i % 2)
        for i in range(10)
    ]
    t0 = time.time()
    results = engine.generate(requests)
    dt = time.time() - t0
    total_tokens = sum(r.steps for r in results)
    for i, r in enumerate(results[:4]):
        print(f"req {i}: {r.steps} tokens -> {r.tokens.tolist()}")
    stats = engine.stats
    print(f"\n{len(requests)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s end-to-end, "
          f"{stats.tokens_per_sec:.1f} tok/s windowed)")
    print(f"slots: {stats.prefills} admissions over buckets {stats.buckets}, "
          f"{stats.slot_steps} decoded lanes for "
          f"{total_tokens - stats.prefills} decode tokens "
          f"(policy={args.policy})")
    if ladder is not None:
        print(f"elastic: dp={ladder.widths} reshards={stats.reshards}")


if __name__ == "__main__":
    main()
