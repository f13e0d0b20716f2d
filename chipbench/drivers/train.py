"""Adaptive LM training: ``StepEngine.step`` driven by a tick-fired DiveBatch
``AdaptationProgram``, the loop the repository's LM callers run (step; every
``tick_every`` steps read the diversity signals, observe, resize).

Set-up builds the one engine and state the window uses, from the seed:
weights on the device in one jitted call, a pool of distinct training rows
on the host.  On more than one chip the cell trains data-parallel with its
state sharded (dp = fsdp over one mesh axis of the cell's devices): the
state is made in place on the plan's shardings, each batch is split over
the devices by rows, and every step and tick runs under the plan.  Steps
1-3 go through the window's own call at ``check_batch`` sequences (``num_micro``
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
same seed on the same rows.  The reference is the module the configuration
names (``harness.load_reference``).  It takes its gradients one row at a
time and averages them, so that it fits beside nothing but its own state;
its leaves are sharded over the cell's devices.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from chipbench import harness
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


def leaf_shardings(tree, devices):
    """Each leaf of ``tree`` (arrays or shapes) over a one-axis mesh of
    ``devices``, split along its last axis that the device count divides,
    whole on every device where none does."""
    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    n = len(devices)

    def one(x):
        for ax in reversed(range(len(x.shape))):
            if x.shape[ax] % n == 0:
                return NamedSharding(mesh, P(*([None] * ax), "data"))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, tree)


def micro_groups(n: int, micro_batch: int, devices: int) -> list[list[int]]:
    """The rows of each microbatch of an ``n``-row batch, as data-parallel
    gradient accumulation lays them out: each device holds a consecutive
    share of the batch and takes ``micro_batch / devices`` rows of it, in
    order, into each microbatch."""
    share, per = n // devices, max(micro_batch // devices, 1)
    return [[d * share + g * per + j for d in range(devices) for j in range(per)]
            for g in range(share // per)]


def moment_diversity(q: float, r: float, n: int, groups: int) -> float:
    """DiveBatch's moment estimate of the diversity from ``q``, the sum over
    microbatches of the squared norm of their gradient sums, and ``r``, the
    squared norm of the whole batch's gradient sum (the estimator's own
    equations, core/diversity.py's description)."""
    m = n / groups
    if m == 1 or n - m < 0.5:  # one row a microbatch: q is the rows' own sum
        return q / r
    mu = max((r - q) / (n * (n - m)), 0.0)
    return n * max(q / n - (m - 1.0) * mu, 1e-20) / r


def reference_follow(ref, dims: dict, seed: int, weights_dtype: str, batches, lr: float,
                     momentum: float, devices, precision: str = "f32", rows: int | None = None,
                     lost=(), micro_batch: int = 1) -> dict:
    """SGD with momentum through ``batches`` on the reference module ``ref``,
    each step's gradient the mean of its rows' gradients taken one row at a
    time: per-step losses, the first step's diversity (microbatches of
    ``micro_batch`` rows laid out over ``devices`` as ``micro_groups`` says;
    with one row a microbatch, the sum of the rows' squared gradient norms
    over the squared norm of their sum), per-leaf norms of the first
    gradient and of the change.  ``rows`` keeps only the first ``rows`` of
    each batch (the half-batch fault); the rows in ``lost`` add nothing to
    the gradient, which is still taken over all rows (one chip's share lost
    before the mean).  Every leaf is sharded over ``devices``
    (``leaf_shardings``)."""
    key = harness.key_of(seed)
    made = lambda key: jax.tree.map(lambda x: x.astype(jnp.float32),
                                    ref.make_weights(dims, key, weights_dtype))
    shard = leaf_shardings(jax.eval_shape(made, key), devices)
    whole = NamedSharding(next(iter(jax.tree.leaves(shard))).mesh, P())
    put = lambda x: jax.device_put(x, whole)
    made = jax.jit(made, out_shardings=shard)
    grad = lambda p, tokens, targets: jax.value_and_grad(
        lambda p: ref.loss(dims, p, tokens, targets, precision))(p)

    @functools.partial(jax.jit, donate_argnums=(1,), out_shardings=(shard, None, None))
    def accumulate(p, m, tokens, targets, w):
        loss, g = grad(p, tokens, targets)
        m = jax.tree.map(lambda m, g: m + w * g, m, g)
        return m, loss, sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))

    @functools.partial(jax.jit, donate_argnums=(1, 2), out_shardings=(shard, shard, None))
    def accumulate_sum(p, m, acc, tokens, targets, w, k):
        """``accumulate``, also adding the row's gradient (times ``k``) into
        its microbatch's sum ``acc``."""
        loss, g = grad(p, tokens, targets)
        m = jax.tree.map(lambda m, g: m + w * g, m, g)
        return m, jax.tree.map(lambda a, g: a + k * g, acc, g), loss

    sq_norm = jax.jit(lambda t: sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(t)))
    decay = jax.jit(lambda m: jax.tree.map(lambda x: momentum * x, m), donate_argnums=(0,),
                    out_shardings=shard)
    update = jax.jit(lambda p, m, lr: jax.tree.map(lambda p, m: p - lr * m, p, m),
                     donate_argnums=(0,), out_shardings=shard)
    p = made(key)
    m = jax.tree.map(jnp.zeros_like, p)
    losses, first, diversity = [], None, None
    for b in batches:
        tokens, targets = np.asarray(b["tokens"])[:rows], np.asarray(b["targets"])[:rows]
        n = len(tokens)
        groups = micro_groups(n, micro_batch, len(devices))
        m = decay(m)
        loss, sq = 0.0, 0.0
        for group in groups:
            acc = jax.tree.map(jnp.zeros_like, p) if len(group) > 1 else None
            for r in group:
                w = jnp.float32(0.0 if r in lost else 1.0 / n)
                x, y = put(tokens[r:r + 1]), put(targets[r:r + 1])
                if acc is None:
                    m, loss_r, sq_r = accumulate(p, m, x, y, w)
                    sq += 0.0 if r in lost else float(sq_r)
                else:
                    m, acc, loss_r = accumulate_sum(p, m, acc, x, y, w,
                                                    jnp.float32(r not in lost))
                loss += float(loss_r) / n
            if acc is not None:
                sq += float(sq_norm(acc))
                del acc
        losses.append(loss)
        if first is None:  # momentum starts at zero: after step 1 it is the mean gradient
            first = _leaf_norms(m)
            diversity = moment_diversity(sq, n * n * sum(x * x for x in first), n, len(groups))
        p = update(p, m, jnp.float32(lr))
    del m
    p0 = made(key)
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


def plan_of(devices):
    """None on one device; on more, dp = fsdp over one ``data`` axis of them."""
    if len(devices) == 1:
        return None
    from repro.dist.plan import ShardingPlan, make_mesh

    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    return ShardingPlan(mesh=mesh, dp=("data",), fsdp=("data",), tp=None)


def under(plan):
    """The plan's context (every step and tick runs inside it), or none."""
    if plan is None:
        return contextlib.nullcontext()
    from repro.dist.plan import use_plan

    return use_plan(plan)


def build(cell: harness.Cell, plan=None):
    """Engine, state, rows and the pieces the loop needs, from the seed.
    The state is made in one jitted call, the seed's key its argument (so
    one program serves every seed); under a plan straight onto the
    shardings the plan gives it (``elastic.reshard.state_shardings``, where
    ``reshard`` would move it), so no device holds it whole."""
    from repro.configs import get_config
    from repro.optim import sgd
    from repro.train import StepEngine, init_state

    c, job, sp = cell.config, cell.traffic, cell.spec
    ref = harness.load_reference(cell)
    dims = ref.dims_of(c)
    cfg = get_config(c["arch"]).replace(**c["program"])
    pool = markov.lm_pool(dims["vocab_size"], job["rows"], job["seq_len"], cell.seed,
                          branch=job["branch"], zipf_s=job["zipf_s"])
    opt = sgd(momentum=job["momentum"], state_dtype=jnp.dtype(sp["momentum_dtype"]))
    div_dtype = jnp.dtype(sp["diversity_dtype"])
    make = lambda key: init_state(ref.make_weights(dims, key, c["program"]["param_dtype"]),
                                  opt, div_dtype=div_dtype)
    key = harness.key_of(cell.seed)
    if plan is None:
        state = jax.jit(make)(key)
    else:
        from repro.elastic.reshard import state_shardings

        out = state_shardings(jax.eval_shape(make, key), plan)
        state = jax.jit(make, out_shardings=out)(key)
    dp = 1 if plan is None else plan.dp_size
    if job["micro_batch"] % dp:
        raise ValueError(f"micro_batch {job['micro_batch']} does not split over {dp} devices")
    engine = StepEngine.for_lm(cfg, opt, micro_batch=job["micro_batch"], dp_size=dp,
                               attn_impl=cfg.attn_impl,
                               grad_accum_dtype=jnp.dtype(sp["grad_accum_dtype"]))
    return dims, pool, engine, state


class Feed:
    """Distinct rows in order, wrapping round the pool; under a plan each
    batch is split over its devices by rows."""

    def __init__(self, pool: dict, devices, plan=None):
        self.pool, self.next = pool, 0
        self.sharding = (jax.sharding.SingleDeviceSharding(devices[0]) if plan is None
                         else NamedSharding(plan.mesh, P(plan.dp)))

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
    ref = harness.load_reference(cell)
    made = jax.jit(lambda key: ref.make_weights(ref.dims_of(cell.config), key,
                                                cell.config["program"]["param_dtype"]),
                   out_shardings=jax.tree.map(lambda x: x.sharding, state.params))
    prog["change"] = _diff_norms(state.params, made(harness.key_of(cell.seed)))
    return state, metrics, prog, rows, lr


def lost_rows(cell: harness.Cell) -> range:
    """The rows of a ``check_batch`` batch that its last device holds (a
    batch is split over the devices by rows, in order)."""
    n = cell.traffic["check_batch"]
    return range(n - n // len(cell.devices), n)


def reference(cell: harness.Cell, rows, lr: float, precision: str = "f32",
              keep_rows: int | None = None, lost=()) -> dict:
    ref = harness.load_reference(cell)
    return reference_follow(ref, ref.dims_of(cell.config), cell.seed,
                            cell.config["program"]["param_dtype"], rows, lr,
                            cell.traffic["momentum"], cell.devices, precision, keep_rows, lost,
                            cell.traffic["micro_batch"])


def run(cell: harness.Cell, t_start: float) -> dict:
    counter = harness.CompileCounter()
    plan = plan_of(cell.devices)
    with under(plan):
        rec, state, prog, first_rows, lr0 = _train(cell, plan, counter, t_start)
    rec["memory_peak_bytes"] = harness.memory_peak_bytes(cell.devices)
    del state
    gc.collect()
    ref = reference(cell, first_rows, lr0)
    got = compare(prog, ref)
    print("schedule " + _runs(rec["schedule"]), file=sys.stderr, flush=True)
    rec["checks"] = [{"name": k, "value": v, "limit": cell.limits[k]} for k, v in got.items()]
    return rec


def _train(cell: harness.Cell, plan, counter, t_start: float):
    """Set-up, the window and the traced stretch: the record, the state,
    and what the comparison needs."""
    from repro.adapt import Clock, read_signals

    job = cell.traffic
    dims, pool, engine, state = build(cell, plan)
    feed = Feed(pool, cell.devices, plan)
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
        "rows_per_device": job["micro_batch"] // len(cell.devices),
        "chips": len(cell.devices), "peak": cell.peak,
        "counts": harness.counts_name(cell.config),
    }

    if cell.trace:
        traced = []
        with harness.profiled(cell) as tr:
            for _ in range(cell.spec["trace_steps"]):
                state, metrics = engine.step(state, feed.batch(m), program.lr)
                traced.append(m // job["micro_batch"])
            jax.block_until_ready(state)
        rec["trace"], rec["traced_num_micro"] = tr, traced
    return rec, state, prog, first_rows, lr0


def _runs(schedule: list[int]) -> str:
    """A schedule as ``size x steps`` runs: ``1x4 2x4 8x36``."""
    out = []
    for m in schedule:
        if out and out[-1][0] == m:
            out[-1][1] += 1
        else:
            out.append([m, 1])
    return " ".join(f"{m}x{k}" for m, k in out)
