"""Adaptive LM training: ``StepEngine.step`` driven by a tick-fired DiveBatch
``AdaptationProgram``, the loop the repository's LM callers run (step; every
``tick_every`` steps read the diversity signals, observe, resize).

Set-up builds the one engine and state the window uses, from the seed:
weights on the device in one jitted call, a pool of distinct training rows
on the host.  Steps 1-3 go through the window's own call at ``check_batch``
sequences, the bucket the window spends most of its time in (``num_micro``
microbatches accumulated in the step), with the window's own tick after
step 1; they are what the reference follows.  Then one step at each further
``num_micro`` bucket the policy may visit compiles (or loads) its program.
The window restarts the schedule at ``m0`` and runs for ``--seconds``; its
last step ends in ``block_until_ready``.

The comparison, after the window and with the engine's state freed: the
loss of steps 1-3, the diversity the tick read after step 1, the norm of the
first gradient as the optimizer holds it after step 1 (SGD momentum starts
at zero, so it is that gradient), and the norm of each parameter's change
after step 3, each leaf against the plain float32 reference run from the
same seed on the same rows.  The reference takes its gradients one row at a
time and averages them, so that it fits beside nothing but its own state.
"""

from __future__ import annotations

import functools
import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.reference import llama
from chipbench.traffic import markov


@jax.jit
def _norms(t):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(t)]


@jax.jit
def _gaps(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def _leaf_norms(tree) -> list[float]:
    return [float(x) for x in _norms(tree)]


def _diff_norms(a, b) -> list[float]:
    return [float(x) for x in _gaps(a, b)]


def leaf_gaps(prog: list[float], ref: list[float], skip=()) -> float:
    """Worst leaf of |program norm - reference norm|, each against the larger
    of that leaf's reference norm and the median leaf's."""
    med = float(np.median(ref))
    return max(abs(p - r) / max(r, med) for i, (p, r) in enumerate(zip(prog, ref))
               if i not in skip)


def moving_leaves(ref_grad: list[float]) -> set[int]:
    """Leaves left out of the change: a reference gradient under a
    thousandth of the median leaf's moves only by round-off."""
    med = float(np.median(ref_grad))
    return {i for i, g in enumerate(ref_grad) if g < 1e-3 * med}


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


def reference_follow(dims: dict, seed: int, weights_dtype: str, batches, lr: float,
                     momentum: float, precision: str = "f32", rows: int | None = None) -> dict:
    """SGD with momentum through ``batches`` on the reference, each step's
    gradient the mean of its rows' gradients taken one row at a time:
    per-step losses, the first step's diversity (sum of the rows' squared
    gradient norms over the squared norm of their sum), per-leaf norms of
    the first gradient and of the change.  ``rows`` keeps only the first
    ``rows`` of each batch (the half-batch fault)."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def accumulate(p, m, tokens, targets, w):
        loss, g = jax.value_and_grad(lambda p: llama.loss(dims, p, tokens, targets, precision))(p)
        m = jax.tree.map(lambda m, g: m + w * g, m, g)
        return m, loss, sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))

    decay = jax.jit(lambda m: jax.tree.map(lambda x: momentum * x, m), donate_argnums=(0,))
    update = jax.jit(lambda p, m, lr: jax.tree.map(lambda p, m: p - lr * m, p, m),
                     donate_argnums=(0,))
    p = jax.tree.map(lambda x: x.astype(jnp.float32),
                     llama.make_weights(dims, seed, weights_dtype))
    m = jax.tree.map(jnp.zeros_like, p)
    losses, first, diversity = [], None, None
    for b in batches:
        tokens, targets = np.asarray(b["tokens"])[:rows], np.asarray(b["targets"])[:rows]
        n = len(tokens)
        m = decay(m)
        loss, sq = 0.0, 0.0
        for r in range(n):
            m, loss_r, sq_r = accumulate(p, m, jnp.asarray(tokens[r:r + 1]),
                                         jnp.asarray(targets[r:r + 1]), jnp.float32(1.0 / n))
            loss, sq = loss + float(loss_r) / n, sq + float(sq_r)
        losses.append(loss)
        if first is None:  # momentum starts at zero: after step 1 it is the mean gradient
            first = _leaf_norms(m)
            diversity = sq / (n * n * sum(x * x for x in first))
        p = update(p, m, jnp.float32(lr))
    del m
    p0 = jax.tree.map(lambda x: x.astype(jnp.float32), llama.make_weights(dims, seed, weights_dtype))
    change = _diff_norms(p, p0)
    del p, p0
    return {"losses": losses, "diversity": diversity, "grad": first, "change": change}


def compare(prog: dict, ref: dict) -> dict:
    """The four compared numbers, program (or control) against reference."""
    skip = moving_leaves(ref["grad"])
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"])),
        "diversity_gap": abs(prog["diversity"] - ref["diversity"]) / ref["diversity"],
        "first_grad_norm_gap": leaf_gaps(prog["grad"], ref["grad"]),
        "change_norm_gap": leaf_gaps(prog["change"], ref["change"], skip),
    }


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def build(cell: harness.Cell):
    """Engine, state, rows and the pieces the loop needs, from the seed."""
    from repro.configs import get_config
    from repro.optim import sgd
    from repro.train import StepEngine, init_state

    c, job, sp = cell.config, cell.traffic, cell.spec
    dims = llama.dims_of(c)
    cfg = get_config(c["arch"]).replace(**c["program"])
    pool = markov.lm_pool(dims["vocab_size"], job["rows"], job["seq_len"], cell.seed,
                          branch=job["branch"], zipf_s=job["zipf_s"])
    opt = sgd(momentum=job["momentum"], state_dtype=jnp.dtype(sp["momentum_dtype"]))
    weights = llama.make_weights(dims, cell.seed, c["program"]["param_dtype"])
    state = init_state(weights, opt, div_dtype=jnp.dtype(sp["diversity_dtype"]))
    del weights
    engine = StepEngine.for_lm(cfg, opt, micro_batch=job["micro_batch"], attn_impl=cfg.attn_impl,
                               grad_accum_dtype=jnp.dtype(sp["grad_accum_dtype"]))
    return dims, pool, engine, state


class Feed:
    """Distinct rows in order, wrapping round the pool."""

    def __init__(self, pool: dict, devices):
        self.pool, self.next, self.sharding = pool, 0, jax.sharding.SingleDeviceSharding(devices[0])

    def rows(self, n: int) -> np.ndarray:
        idx = (self.next + np.arange(n)) % len(self.pool["tokens"])
        self.next += n
        return idx

    def batch(self, n: int) -> dict:
        idx = self.rows(n)
        return {k: jax.device_put(v[idx], self.sharding) for k, v in self.pool.items()}


def make_program(job: dict):
    from repro.adapt import AdaptationProgram, DiveBatchPolicy

    policy = DiveBatchPolicy(job["m0"], job["m_max"], delta=job["delta"], dataset_size=None,
                             granule=job["micro_batch"], on_tick=True)
    return AdaptationProgram(policy, base_lr=job["lr"], estimator="moment",
                             tick_every=job["tick_every"])


def first_steps(cell: harness.Cell, engine, state, feed: Feed):
    """Steps 1-3 at ``check_batch`` sequences through the window's own call,
    from the seed's weights: their losses, the diversity the window's tick
    reads after step 1, the first gradient's leaf norms as the optimizer
    holds it, and the leaf norms of the change after step 3."""
    from repro.adapt import read_signals

    n, lr = cell.traffic["check_batch"], make_program(cell.traffic).lr
    rows, prog = [], {"losses": []}
    for i in range(3):
        b = feed.batch(n)
        rows.append({k: np.asarray(v) for k, v in b.items()})
        state, metrics = engine.step(state, b, lr)
        prog["losses"].append(float(metrics["loss"]))
        if i == 0:
            prog["grad"] = _leaf_norms(state.opt_state.momentum)
            sig, state = read_signals(state, "moment", reset=True, batch_size=n,
                                      loss=prog["losses"][0])
            prog["diversity"] = sig.diversity
    p0 = llama.make_weights(llama.dims_of(cell.config), cell.seed,
                            cell.config["program"]["param_dtype"])
    prog["change"] = _diff_norms(state.params, p0)
    return state, metrics, prog, rows, lr


def reference(cell: harness.Cell, rows, lr: float, precision: str = "f32",
              keep_rows: int | None = None) -> dict:
    return reference_follow(llama.dims_of(cell.config), cell.seed,
                            cell.config["program"]["param_dtype"], rows, lr,
                            cell.traffic["momentum"], precision, keep_rows)


def run(cell: harness.Cell, t_start: float) -> dict:
    from repro.adapt import Clock, read_signals

    job = cell.traffic
    counter = harness.CompileCounter()
    dims, pool, engine, state = build(cell)
    feed = Feed(pool, cell.devices)
    state, metrics, prog, first_rows, lr0 = first_steps(cell, engine, state, feed)

    # one step at every other bucket the policy may visit, then a tick
    m = job["m0"]
    while m <= job["m_max"]:
        if m != job["check_batch"]:
            state, metrics = engine.step(state, feed.batch(m), lr0)
        m *= 2
    _, state = read_signals(state, "moment", reset=True, batch_size=job["m0"],
                            loss=float(metrics["loss"]))

    # the window
    program = make_program(job)
    tick_s, schedule, ticks = [], [], []
    seq = job["seq_len"]
    compiles0 = counter.count
    step_no, tokens = 0, 0
    t0 = time.perf_counter()
    t_end = t0 + cell.seconds
    m = program.batch_size
    while time.perf_counter() < t_end:
        state, metrics = engine.step(state, feed.batch(m), program.lr)
        step_no += 1
        tokens += m * seq
        schedule.append(m)
        if step_no % job["tick_every"] == 0:
            jax.block_until_ready(state)
            ta = time.perf_counter()
            sig, state = read_signals(state, "moment", reset=True, batch_size=m,
                                      loss=float(metrics["loss"]))
            program.observe(sig, Clock(epoch=0, step=step_no, boundary="tick"))
            m = program.batch_size
            tick_s.append(time.perf_counter() - ta)
            ticks.append({"step": step_no, "diversity": sig.diversity, "loss": sig.loss,
                          "batch": m})
    jax.block_until_ready(state)
    t1 = time.perf_counter()
    rec = {
        "setup_s": t0 - t_start, "window_s": t1 - t0, "tokens": tokens, "steps": step_no,
        "schedule": schedule, "ticks": ticks, "tick_s": tick_s,
        "window_compiles": counter.count - compiles0, "attempted": step_no,
        "failed": sum(not np.isfinite(t["loss"]) for t in ticks),
        "dims": dims, "seq_len": seq, "micro_batch": job["micro_batch"],
        "chips": len(cell.devices), "peak": cell.peak,
    }

    if cell.trace:
        traced = []
        with harness.profiled(cell) as tr:
            for _ in range(cell.spec["trace_steps"]):
                state, metrics = engine.step(state, feed.batch(m), program.lr)
                traced.append(m // job["micro_batch"])
            jax.block_until_ready(state)
        rec["trace"], rec["traced_num_micro"] = tr, traced

    rec["memory_peak_bytes"] = harness.memory_peak_bytes(cell.devices)
    del state, engine, metrics
    gc.collect()
    ref = reference(cell, first_rows, lr0)
    got = compare(prog, ref)
    print("schedule " + _runs(schedule), file=sys.stderr, flush=True)
    rec["checks"] = [{"name": k, "value": v, "limit": cell.limits[k]} for k, v in got.items()]
    return rec


def _runs(schedule: list[int]) -> str:
    """A schedule as ``size x steps`` runs: ``1x4 2x4 8x36``."""
    out = []
    for m in schedule:
        if out and out[-1][0] == m:
            out[-1][1] += 1
        else:
            out.append([m, 1])
    return " ".join(f"{m}x{k}" for m, k in out)
