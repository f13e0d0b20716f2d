"""Host-side training shell — the paper's Algorithm 1 end to end.

The ``Trainer`` is a thin host loop over ``train/engine.py::StepEngine``: it
owns only the HOST decisions — the adaptation program, the data cursor,
checkpoint/resume, and eval cadence. All device work (the SGD step, the
diversity-tier accumulation, buffer donation, the per-bucket compile cache)
lives in the engine; each mini-batch is one SGD step (exactly Algorithm 1:
adapting the batch size changes the *step* granularity), and the only
per-step host transfer is the scalar loss.

Adaptation runs through ``repro.adapt`` — the single adaptation path.  The
4th constructor argument accepts either an ``adapt.AdaptationProgram`` (the
new API) or a legacy ``core.AdaptiveBatchController`` (the deprecated shim
over a program); both drive the identical program underneath.  Boundaries:

  * EPOCH ends (always): signals are read off the in-jit accumulators (one
    stacked scalar transfer), fed to ``program.observe``, and the
    accumulators reset — the classic DiveBatch cadence.
  * Every-k-steps TICKS (``program.tick_every > 0``) and injected EVENTS
    (``Trainer.inject_event``, e.g. a supervisor Watchdog flag): observed
    BETWEEN steps with the *running* accumulators (no reset).  A mid-epoch
    decision resizes the batch — phase-aligned so the new size continues
    the epoch permutation at an exact multiple of itself — reshards the
    elastic rung, and retargets lr/estimator, all before the next step.

API stability: the ``Trainer`` constructor and ``run``/``run_epoch``/
``save``/``resume`` signatures are unchanged; ``trainer.params`` etc. are
read-only views of the engine-owned ``TrainState``.

Elastic mode (``elastic=MeshLadder(...)``): the ladder co-adapts the device
footprint with the batch size — at any boundary that resizes the batch
(epoch end OR mid-epoch), the state is resharded onto the widest rung whose
dp width keeps the per-device microbatch >= the ladder granule
(``repro.elastic``); the engine's compile cache keys by (bucket, rung). A
``Decision`` carrying an explicit ``rung`` overrides the batch-derived one
(straggler evacuation).  The feed path double-buffers device transfers
(``data.pipeline.prefetch``; ``prefetch="thread"`` additionally overlaps
the host-side numpy gather, ``prefetch=False`` reverts to the synchronous
put-per-step loop — the trajectory is identical in all three modes).

Checkpointing captures the FULL adaptive state (program schema v2; v1
pre-redesign checkpoints restore unchanged); ``Trainer.resume()`` restores
mid-training with the identical remaining trajectory (tests assert this).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.adapt import (
    AdaptationProgram,
    Clock,
    Signals,
    ThroughputWindow,
    read_signals,
)
from repro.ckpt import CheckpointManager
from repro.core import AdaptiveBatchController, diversity
from repro.data import ArrayDataset, Cursor, EpochLoader
from repro.data.pipeline import (
    epoch_permutation,
    prefetch as prefetch_iter,
    put_global_batch,
)
from repro.dist.plan import current_plan
from repro.elastic import MeshLadder, place, reshard
from repro.obs import runlog as runlog_lib
from repro.obs import trace as trace_lib
from repro.train.engine import ModelFns, StepEngine, eval_fn_for
from repro.train.state import TrainState, init_state
from repro.utils.logging import get_logger

log = get_logger("train")

__all__ = ["ModelFns", "EpochRecord", "Trainer"]

#: estimator tiers that run inside the jitted step
_INJIT_TIERS = ("exact", "gram", "moment")


@dataclasses.dataclass
class EpochRecord:
    epoch: int
    batch_size: int
    lr: float
    train_loss: float
    val_loss: float
    val_metrics: dict
    diversity: float | None
    steps: int
    wall_s: float


class Trainer:
    def __init__(
        self,
        fns: ModelFns,
        params: Any,
        optimizer,
        controller: AdaptiveBatchController | AdaptationProgram,
        train_data: ArrayDataset,
        val_data: ArrayDataset,
        *,
        estimator: str = "exact",  # exact | gram | moment | oracle | none
        seed: int = 0,
        psn_microbatch: int = 256,
        ckpt: CheckpointManager | None = None,
        ckpt_every: int = 0,
        donate: bool = True,
        engine: StepEngine | None = None,
        elastic: MeshLadder | None = None,
        prefetch: bool | str = True,
        tracer=None,
        runlog=None,
    ):
        self.fns = fns
        # telemetry sinks (repro.obs); rebound for real at the end of init
        # via bind_obs once the engine/program exist
        self._tracer = trace_lib.NULL
        self._runlog = runlog_lib.NULL
        self.optimizer = optimizer
        self.controller = controller  # legacy view; may BE the program
        self.adapt = (
            controller.program
            if isinstance(controller, AdaptiveBatchController)
            else controller
        )
        self.train_data = train_data
        self.val_data = val_data
        self.estimator = estimator
        self.seed = seed
        self.psn_microbatch = psn_microbatch  # exact-tier vmap width / oracle chunk
        self.ckpt = ckpt
        self.ckpt_every = ckpt_every
        self.cursor = Cursor()
        self.history: list[EpochRecord] = []
        self._events: list[str] = []  # injected, consumed between steps
        # Donation invalidates the buffers passed to each step, so the state
        # lives in exactly one place: self.state, replaced every step
        # (init_state makes the leaves donation-ready jax Arrays).
        self.state: TrainState = init_state(params, optimizer)
        self._plan = current_plan()
        if elastic is not None and self._plan is not None:
            raise ValueError(
                "Trainer(elastic=...) under an ambient dist plan is ambiguous: "
                "the ladder owns the sharding plan per rung — drop the "
                "use_plan context (or the elastic ladder)"
            )
        self._elastic = elastic
        self._rung = None
        if prefetch not in (True, False, "thread"):
            raise ValueError(
                f"prefetch must be True, False, or 'thread', got {prefetch!r}"
            )
        self._prefetch = prefetch
        # windowed steps/s for Signals.throughput: a policy reacting to a
        # straggler sees the recent rate, not the run-global average
        self._thru = ThroughputWindow()
        self._shardings: dict[tuple[int, int], Any] = {}
        self.engine = engine or self._build_engine(donate)
        # an injected engine may lack an eval fn; the Trainer owns the fns
        self.engine.ensure_eval_fn(eval_fn_for(fns))
        self.bind_obs(tracer=tracer, runlog=runlog)
        if self._elastic is not None:
            # initial placement: the rung for the starting batch size
            self._ensure_rung(self.adapt.batch_size)

    def _build_engine(self, donate: bool) -> StepEngine:
        # A ladder may supply its own rung-aware engine (duck-typed so the
        # base Trainer never imports repro.pod): PodLadder compiles the
        # shard_map'd compressed cross-pod step on pods>1 rungs.
        engine_for = getattr(self._elastic, "engine_for", None)
        if engine_for is not None:
            return engine_for(
                self.fns,
                self.optimizer,
                estimator=self.estimator,
                diversity_on=self.adapt.needs_diversity,
                donate=donate,
                psn_chunk=self.psn_microbatch,
            )
        return StepEngine.for_model_fns(
            self.fns,
            self.optimizer,
            estimator=self.estimator,
            diversity_on=self.adapt.needs_diversity,
            dp_size=self._plan.dp_size if self._plan else 1,
            donate=donate,
            psn_chunk=self.psn_microbatch,
        )

    # -- read-only views of the engine-owned state (API compatibility) -------
    @property
    def params(self):
        return self.state.params

    @property
    def opt_state(self):
        return self.state.opt_state

    @property
    def div_state(self):
        return self.state.div_state

    @property
    def rung(self):
        """The live elastic ladder rung (None outside elastic mode)."""
        return self._rung

    @property
    def elastic(self):
        """The elastic ladder driving this trainer (None outside elastic
        mode) — the supervisor reaches pod health through this."""
        return self._elastic

    # ------------------------------------------------------------------
    @property
    def _live_plan(self):
        """The plan batches/state live on: the elastic rung's when a ladder
        drives the run, else the ambient dist plan (None single-device)."""
        return self._rung.plan if self._rung is not None else self._plan

    def bind_obs(self, *, tracer=None, runlog=None) -> None:
        """Attach telemetry sinks (``repro.obs``) to the trainer, its engine,
        and its adaptation program.  ``None`` leaves a sink unchanged — the
        supervisor rebinds the same sinks onto every rebuilt Trainer so one
        trace/run log spans restarts."""
        if tracer is not None:
            self._tracer = tracer
            self.engine.tracer = tracer
        if runlog is not None:
            self._runlog = runlog
            self.engine.runlog = runlog
        bind = getattr(self.adapt, "bind_obs", None)
        if bind is not None:
            bind(tracer=tracer, runlog=runlog)

    def inject_event(self, name: str) -> None:
        """Queue an external event (e.g. a supervisor Watchdog straggler
        flag).  Consumed BETWEEN steps at the next opportunity: the adapt
        program observes it with ``boundary='event'`` and may resize /
        reshard / retune before the following step."""
        self._events.append(str(name))
        if self._runlog.enabled:
            self._runlog.emit("inject", name=str(name),
                              epoch=self.cursor.epoch,
                              step=self.engine.stats.steps)

    def _ensure_rung(self, batch_size: int) -> None:
        """Elastic transition: move the state onto the ladder rung for
        ``batch_size`` — called at any boundary that resizes the batch
        (epoch end or mid-epoch). Strict no-op when the rung is unchanged
        (reshard returns the identical state object)."""
        if self._elastic is None:
            return
        self._transition(self._elastic.rung_for_batch(batch_size),
                         note=f"for batch {batch_size}")

    def _transition(self, rung, note: str = "") -> None:
        if self._rung is not None and rung.index == self._rung.index:
            return
        src = self._rung
        # the initial placement must NOT donate: the state still aliases the
        # caller-passed params at that point (transitions own their buffers)
        with self._tracer.span("reshard", scope="train",
                               src=src.index if src else None,
                               dst=rung.index, dp=rung.dp):
            self.state = reshard(
                self.state, src.plan if src else None, rung.plan,
                donate=self.engine.donate and src is not None,
            )
        self._rung = rung
        self.engine.rung = rung.index
        # ladder-specific state (e.g. PodLadder's compression residuals) is
        # installed/dropped AFTER the reshard so it lands on the new mesh
        self.state = self._elastic.adapt_state(self.state, src, rung)
        if src is not None:  # initial placement is not a transition
            self.engine.stats.reshards += 1
            if self._runlog.enabled:
                self._runlog.emit("reshard", scope="train", src=src.index,
                                  dst=rung.index, dp=rung.dp,
                                  epoch=self.cursor.epoch,
                                  step=self.engine.stats.steps,
                                  note=note)
            log.info("elastic: rung %d -> %d (dp %d -> %d) %s",
                     src.index, rung.index, src.dp, rung.dp, note)

    def demote(self, note: str = "pod lost") -> tuple[int | None, int]:
        """Degrade-don't-restart: reshard the LIVE state onto the widest rung
        the (health-filtered) ladder still allows for the current batch size
        — no checkpoint restore, the surviving optimizer/diversity state
        carries straight on.  The supervisor calls this when a pod is lost
        (after marking it in the ladder's health registry).  Returns
        ``(src_rung_index, dst_rung_index)``; a no-op transition returns the
        same index twice."""
        if self._elastic is None:
            raise ValueError("demote() needs an elastic ladder")
        src = self._rung.index if self._rung is not None else None
        self._transition(self._elastic.rung_for_batch(self.adapt.batch_size),
                         note=note)
        return src, self._rung.index

    def _batch_sharding(self, leading: int):
        """NamedSharding over the live plan's dp axes, if one divides the
        batch (memoized by (leading dim, rung) — constant within an epoch)."""
        plan = self._live_plan
        if plan is None:
            return None
        key = (leading, self._rung.index if self._rung is not None else -1)
        if key not in self._shardings:
            self._shardings[key] = (
                NamedSharding(plan.mesh, P(tuple(plan.dp)))
                if leading % plan.dp_size == 0 else None
            )
        return self._shardings[key]

    def _put(self, batch_np: dict) -> dict:
        leading = len(next(iter(batch_np.values())))
        return put_global_batch(batch_np, self._batch_sharding(leading))

    def _oracle_diversity(self) -> float:
        batches = (
            {k: jnp.asarray(v) for k, v in self.train_data.get(idx).items()}
            for idx in np.array_split(
                np.arange(len(self.train_data)),
                max(1, len(self.train_data) // self.psn_microbatch),
            )
        )
        return float(
            diversity.dataset_diversity(
                self.fns.example_loss, self.state.params, batches
            )
        )

    def _throughput(self) -> float:
        """Windowed steps/s (ThroughputWindow); the run-global dispatch
        average only before the first step lands in the window."""
        rate = self._thru.rate()
        return rate if rate is not None else self.engine.stats.dispatch_steps_per_sec

    # -- decision plumbing ----------------------------------------------------
    def _read_estimator(self) -> str:
        """The tier signals are decoded with: the in-jit tier when one is
        active; 'exact' for estimator='none' (unfed accumulators estimate a
        legitimate 0.0, the pre-engine convention); 'moment' for oracle."""
        if self.estimator in _INJIT_TIERS:
            return self.estimator
        return "moment" if self.estimator == "oracle" else "exact"

    def _apply_estimator(self, tier: str | None) -> None:
        """Retarget the diversity tier from a Decision.  On a
        tier-parameterised engine this is just a new compile-cache key —
        (bucket, rung, tier) — so the new tier's buckets compile on first
        use and flipping back onto a seen tier is a cache hit.  Injected
        engines with a single-argument build fall back to the old
        rebuild-the-jit-family behaviour (stats carry over)."""
        if tier is None or tier == self.estimator:
            return
        if tier not in _INJIT_TIERS:
            raise ValueError(
                f"decision estimator must be one of {_INJIT_TIERS}, got {tier!r}"
            )
        log.info("adapt: estimator tier %s -> %s", self.estimator, tier)
        self.estimator = tier
        if self.engine.tiered:
            self.engine.tier = tier
            return
        stats, rung_token = self.engine.stats, self.engine.rung
        self.engine = self._build_engine(self.engine.donate)
        self.engine.ensure_eval_fn(eval_fn_for(self.fns))
        self.engine.stats = stats
        self.engine.rung = rung_token

    def _apply_decision(self, applied) -> None:
        """Non-batch effects of an applied decision (the batch size itself is
        handled by the step loop / epoch boundary)."""
        if applied is None:
            return
        self._apply_estimator(applied.estimator)
        if applied.rung is not None and self._elastic is not None:
            self._transition(self._elastic.rungs[applied.rung], note="(explicit)")

    def _observe_mid_epoch(self, steps_done: int, bsz: int,
                           last_loss: float) -> Any:
        """Tick/event boundaries between steps.  Reads the RUNNING
        accumulators (no reset — the epoch boundary owns the reset) at the
        cost of one stacked-scalar transfer, only when a boundary is due AND
        the policy can actually fire on it (an epoch-only policy under
        --tick-every must not pay a device sync per tick).

        Explicit-rung decisions are NOT applied here: the step loop owns
        that transition because it must also rebuild the feed (prefetched
        batches were put on the old rung's plan)."""
        clock = event = None
        if self._events:
            c = Clock(epoch=self.cursor.epoch, step=self.engine.stats.steps,
                      boundary="event")
            if self.adapt.policy.fires(c):
                event, clock = self._events.pop(0), c
            else:
                # never silently: the injector asked for a reaction the
                # active policy cannot give (and must not block a due tick)
                log.info("adapt: event %r dropped (policy does not fire on "
                         "events)", self._events.pop(0))
        if (clock is None and self.adapt.tick_every
                and steps_done % self.adapt.tick_every == 0):
            c = Clock(epoch=self.cursor.epoch, step=self.engine.stats.steps,
                      boundary="tick")
            if self.adapt.policy.fires(c):
                clock = c
        if clock is None:
            return None
        sig, self.state = read_signals(
            self.state, self._read_estimator(), reset=False,
            batch_size=bsz, loss=last_loss,
            throughput=self._throughput(), event=event, tracer=self._tracer,
        )
        applied = self.adapt.observe(sig, clock)
        if applied is not None:
            self._apply_estimator(applied.estimator)
        return applied

    def _epoch_signals(self, bsz: int, mean_loss: float) -> Signals:
        """Epoch-boundary signals: read + RESET the accumulators (one
        stacked scalar transfer); the oracle tier substitutes the exact
        full-dataset diversity it recomputes at fixed params."""
        if not self.adapt.needs_diversity:
            return Signals(loss=mean_loss, batch_size=bsz,
                           throughput=self._throughput())
        sig, self.state = read_signals(
            self.state, self._read_estimator(), reset=True,
            batch_size=bsz, loss=mean_loss,
            throughput=self._throughput(), tracer=self._tracer,
        )
        if self.estimator == "oracle":
            sig = dataclasses.replace(sig, diversity=self._oracle_diversity())
        return sig

    # ------------------------------------------------------------------
    def run_epoch(self) -> EpochRecord:
        tr = self._tracer
        if not tr.enabled:
            return self._run_epoch()
        with tr.span("epoch", epoch=self.cursor.epoch):
            return self._run_epoch()

    def _run_epoch(self) -> EpochRecord:
        t0 = time.time()
        prog = self.adapt
        bsz = prog.batch_size
        self._ensure_rung(bsz)
        lr = jnp.float32(prog.lr)
        n = len(self.train_data)
        consumed = self.cursor.sample_index or self.cursor.batch_index * bsz
        losses: list[float] = []
        # one O(n) shuffle per epoch, shared by every resize segment's loader
        perm = epoch_permutation(n, self.seed, self.cursor.epoch)

        # One (epoch, batch-size, rung) segment per inner loop: a mid-epoch
        # resize or explicit rung move breaks out, and the next loader
        # continues the SAME permutation at the exact sample offset already
        # consumed.  Tick cadence counts cursor.batch_index (persisted), so
        # a mid-epoch resume keeps the identical tick phase.
        while True:
            target = prog.batch_size
            if target != bsz and consumed % target == 0:
                bsz = target
                lr = jnp.float32(prog.lr)
                self._ensure_rung(bsz)
            loader = EpochLoader(
                self.train_data, bsz, epoch=self.cursor.epoch, seed=self.seed,
                start_sample=consumed, perm=perm,
            )
            if len(loader) == 0:
                break
            feed = (
                prefetch_iter(loader, put=self._put,
                              host_overlap=self._prefetch == "thread")
                if self._prefetch else (self._put(b) for b in loader)
            )
            rebuild = False
            try:
                for batch in feed:
                    self.state, metrics = self.engine.step(self.state, batch, lr)
                    losses.append(float(metrics["loss"]))  # per-step sync
                    self._thru.add(1.0)
                    consumed += bsz
                    self.cursor.batch_index += 1
                    self.cursor.sample_index = consumed
                    applied = self._observe_mid_epoch(
                        self.cursor.batch_index, bsz, losses[-1])
                    if (applied is not None and applied.rung is not None
                            and self._elastic is not None):
                        # explicit rung move: reshard, then rebuild the feed —
                        # buffered batches were put on the OLD rung's plan
                        self._transition(self._elastic.rungs[applied.rung],
                                         note="(explicit)")
                        rebuild = True
                        break
                    # Phase-aligned resize: apply a pending target size once
                    # the consumed offset is a multiple of it, so the new
                    # loader's batches tile the permutation exactly (shrinks
                    # on the pow2 lattice are always aligned; a grow waits at
                    # most target/bsz - 1 steps).  The coupled lr retarget is
                    # deferred WITH the resize — the rescaled lr must land on
                    # the batch it was scaled for, never on pending old-size
                    # steps.
                    target = prog.batch_size
                    if target != bsz:
                        if consumed % target == 0:
                            bsz = target
                            lr = jnp.float32(prog.lr)
                            self._ensure_rung(bsz)
                            rebuild = True
                            break
                    elif applied is not None:
                        lr = jnp.float32(prog.lr)
            finally:
                close = getattr(feed, "close", None)
                if close is not None:
                    close()
            if not rebuild:
                break

        # epoch boundary ------------------------------------------------
        mean_loss = float(np.mean(losses)) if losses else float("nan")
        sig = self._epoch_signals(bsz, mean_loss)
        applied = prog.observe(
            sig, Clock(epoch=self.cursor.epoch, step=self.engine.stats.steps,
                       boundary="epoch"),
        )
        self._apply_decision(applied)

        val = self._put(self.val_data.get(np.arange(len(self.val_data))))
        val_loss, val_metrics = self.engine.evaluate(self.state.params, val)
        rec = EpochRecord(
            epoch=self.cursor.epoch,
            batch_size=prog.batch_size,
            lr=prog.lr,
            train_loss=mean_loss,
            val_loss=float(val_loss),
            val_metrics={k: float(v) for k, v in val_metrics.items()},
            diversity=sig.diversity,
            steps=len(losses),
            wall_s=time.time() - t0,
        )
        self.history.append(rec)
        if self._runlog.enabled:
            self._runlog.emit(
                "epoch", epoch=rec.epoch, steps=rec.steps,
                batch_size=rec.batch_size, lr=rec.lr, loss=rec.train_loss,
                val_loss=rec.val_loss, diversity=rec.diversity,
                gns=sig.gns, throughput=sig.throughput,
                rung=self._rung.index if self._rung is not None else None,
                wall_s=rec.wall_s,
            )
        self.cursor.epoch += 1
        self.cursor.batch_index = 0
        self.cursor.sample_index = 0
        if self.ckpt and self.ckpt_every and self.cursor.epoch % self.ckpt_every == 0:
            self.save()
        return rec

    def run(self, epochs: int, verbose: bool = True) -> list[EpochRecord]:
        for _ in range(epochs):
            rec = self.run_epoch()
            if verbose:
                log.info(
                    "epoch %d: loss=%.4f val=%.4f metrics=%s m=%d lr=%.4g div=%s",
                    rec.epoch, rec.train_loss, rec.val_loss, rec.val_metrics,
                    rec.batch_size, rec.lr,
                    f"{rec.diversity:.4g}" if rec.diversity is not None else "-",
                )
        return self.history

    # ------------------------------------------------------------------
    def save(self):
        assert self.ckpt is not None
        self.ckpt.save(
            step=self.cursor.epoch,
            state={
                "params": self.state.params,
                "opt_state": self.state.opt_state,
                "div_state": self.state.div_state,
            },
            extra={
                "controller": self.adapt.state_dict(),
                "cursor": self.cursor.state_dict(),
                "history": [dataclasses.asdict(r) for r in self.history],
                "step": int(self.state.step),
            },
        )
        if self._runlog.enabled:
            self._runlog.emit("checkpoint", epoch=self.cursor.epoch,
                              step=int(self.state.step))

    def resume(self) -> bool:
        assert self.ckpt is not None
        if self.ckpt.latest_step() is None:
            return False
        # Checkpoints hold logical host tensors; restore places them onto
        # whatever plan is live (elastic.reshard.place) — a checkpoint saved
        # on one rung resumes on any other, or on no plan at all.
        out, extra = self.ckpt.restore(
            {"params": self.state.params, "opt_state": self.state.opt_state,
             "div_state": self.state.div_state}
        )
        # both schema versions load (v1: pre-redesign controller dicts)
        self.adapt.load_state_dict(extra["controller"])
        self.cursor.load_state_dict(extra["cursor"])
        self.history = [EpochRecord(**r) for r in extra.get("history", [])]
        if self._elastic is not None:
            # the restored batch size decides the rung, not the one this
            # (possibly fresh) Trainer started on — pick it BEFORE placing so
            # the state is transferred exactly once
            rung = self._elastic.rung_for_batch(self.adapt.batch_size)
            self._rung = rung
            self.engine.rung = rung.index
        self.state = place(
            TrainState(
                params=out["params"],
                opt_state=out["opt_state"],
                div_state=out["div_state"],
                step=np.asarray(extra.get("step", 0), np.int32),
            ),
            self._live_plan,
        )
        if self._elastic is not None and self._rung is not None:
            # checkpoints never carry ladder-specific state (err_state is
            # transient wire state): re-install it for the restored rung
            self.state = self._elastic.adapt_state(self.state, None, self._rung)
        log.info("resumed from epoch %d", self.cursor.epoch)
        return True
