"""StepEngine — the single compiled training path.

Every trainer in the repo (the host ``Trainer`` shell, ``launch/train.py``,
the multi-pod dry-run, ``examples/train_lm.py``) drives this engine instead
of building its own jits. The engine owns:

  * a compile cache keyed by the power-of-2 batch/``num_micro`` bucket
    (``core/batch_policy.bucket``): a DiveBatch run that adapts the batch
    size across the whole lattice compiles at most
    ``log2(m_max/granule) + 1`` step programs, and a resize back onto an
    already-seen bucket is a cache hit (zero recompilation);
  * buffer donation: steps are compiled with ``donate_argnums=(0,)`` on the
    ``TrainState``, so params/optimizer/diversity buffers are updated in
    place — the steady-state HBM footprint is one state, not two;
  * the scan-based step from ``train/step.py::make_train_step`` with the
    diversity tier folded inside the jit — an epoch performs no per-step
    host transfer beyond the scalar metrics;
  * ``EngineStats``: bucket hit/miss counts, compile count and seconds,
    step count and wall time — the record benchmarks and tests consume.

Sharding: the engine is plan-agnostic. Under ``dist.use_plan`` the caller
passes explicit ``in_shardings``/``out_shardings`` (the dry-run does) or
simply feeds sharded arrays and lets GSPMD propagate (the host path does);
outside a plan everything runs single-device. The engine code is identical
in all three cases — that is the point.
"""

from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.obs import metrics as metrics_lib
from repro.obs import runlog as runlog_lib
from repro.obs import trace as trace_lib
from repro.optim import Optimizer
from repro.train import step as step_lib
from repro.train.state import TrainState

PyTree = Any


@dataclasses.dataclass
class ModelFns:
    """Pure functions defining a (non-LM) trainee.

    batch_loss(params, batch) -> scalar mean loss
    example_loss(params, example) -> scalar (per-sample; for exact/oracle)
    metrics(params, batch) -> dict (e.g. accuracy)   [optional]
    probe_loss(params, probes, batch) -> (loss, acts)  [gram tier, optional]
    probe_specs(params, batch_size) -> probes pytree   [gram tier, optional]
    """

    batch_loss: Callable
    example_loss: Callable | None = None
    metrics: Callable | None = None
    probe_loss: Callable | None = None
    probe_specs: Callable | None = None


def eval_fn_for(fns: ModelFns) -> Callable:
    """The standard eval over ModelFns: (params, batch) -> (loss, metrics)."""

    def eval_fn(params, batch):
        loss = fns.batch_loss(params, batch)
        metrics = fns.metrics(params, batch) if fns.metrics else {}
        return loss, metrics

    return eval_fn


class EngineStats(metrics_lib.StatsView):
    """Observable engine behaviour (consumed by benchmarks/ and tests).

    ``compiles`` counts *step* compilations — one per distinct (bucket, rung,
    tier, batch-signature) tuple; with a fixed batch schema (the normal case)
    that is one per (bucket, rung, tier), so ``compiles ==
    len(set(zip(buckets, rungs, tiers)))`` and the policy's ``max_buckets``
    bound applies per (rung, tier) — ``max_buckets * num_rungs * num_tiers``
    worst case, one per bucket when rung and tier are functions of the
    bucket/run. ``bucket_hits``/``bucket_misses`` count cache lookups;
    ``buckets`` lists the bucket key of each compile in order (a key repeats
    only if the batch schema, rung, or tier changed within a bucket);
    ``reshards`` counts rung transitions applied to the engine-owned state.

    The scalar fields are emitting views over the ``repro.obs.metrics``
    registry: each instance claims a fresh ``train.engine.<n>`` namespace and
    ``REGISTRY.snapshot()`` sees every engine in the process; the legacy
    attribute surface (``stats.compiles += 1``, ``as_dict()``) is unchanged
    (the equivalence test in tests/test_obs.py pins both).
    """

    _COUNTERS = ("compiles", "bucket_hits", "bucket_misses", "steps", "reshards")
    # Time spent *dispatching* steps (``dispatch_wall_s``). jax execution is
    # async: the engine does not block on results (callers decide when to
    # read), so this is NOT end-to-end throughput — benchmarks measure that
    # with their own wall clock around a blocking loop
    # (benchmarks/bench_engine.py).
    _GAUGES = ("compile_s", "dispatch_wall_s")

    def __init__(self, donate: bool = True, *,
                 registry: metrics_lib.Registry | None = None):
        self.donate = donate
        #: the bucket key of each compile, in order
        self.buckets: list[int] = []
        # the rung token active at each compile, parallel to ``buckets`` (all
        # None outside elastic mode). Distinct (bucket, rung) pairs bound the
        # compile count: num_buckets x num_rungs worst case, and exactly one
        # per bucket when the rung is a pure function of the bucket (a
        # MeshLadder driven by the same granule as the batch policy).
        self.rungs: list = []
        # the estimator-tier token active at each compile, parallel to
        # ``buckets`` (None for engines whose build is not tier-parameterised).
        # A Decision.estimator flip is a new cache key, not an engine rebuild:
        # flipping back onto an already-compiled (bucket, rung, tier) is a hit.
        self.tiers: list = []
        self._init_metrics("train.engine", registry)

    @property
    def dispatch_steps_per_sec(self) -> float:
        return self.steps / self.dispatch_wall_s if self.dispatch_wall_s > 0 else 0.0

    def as_dict(self) -> dict:
        return {
            "compiles": self.compiles,
            "bucket_hits": self.bucket_hits,
            "bucket_misses": self.bucket_misses,
            "steps": self.steps,
            "compile_s": self.compile_s,
            "reshards": self.reshards,
            "dispatch_wall_s": self.dispatch_wall_s,
            "donate": self.donate,
            "buckets": list(self.buckets),
            "rungs": list(self.rungs),
            "tiers": list(self.tiers),
            "dispatch_steps_per_sec": self.dispatch_steps_per_sec,
        }


class StepEngine:
    """Bucketed, donation-aware compile cache around ``make_train_step``.

    ``build_step(key)`` returns the (untraced) step function for one bucket
    key; ``bucket_of(batch)`` maps a host batch to its key (default: the
    leading dim of the first leaf, which the batch policies already snap to
    the pow2 lattice).  ``build_step`` may instead take ``(key, tier)`` —
    then the engine is *tier-parameterised*: setting ``engine.tier`` keys
    the compile cache by (bucket, rung, tier), so a ``Decision.estimator``
    flip compiles the new tier's buckets on first use and every flip back
    onto a seen tier is a cache hit (the old behaviour rebuilt the whole
    jit family per flip).  A third positional parameter, ``(key, tier,
    rung)``, makes the build *rung-aware*: the active ``engine.rung`` token
    is passed through so the build can return a structurally different step
    program per rung (``repro.pod.PodLadder`` compiles a shard_map'd
    compressed cross-pod step on ``pods > 1`` rungs and the plain step
    elsewhere); the jit cache then keys by (bucket, tier, rung).
    """

    def __init__(
        self,
        build_step: Callable[[int], Callable],
        *,
        bucket_of: Callable[[PyTree], int] | None = None,
        donate: bool = True,
        in_shardings=None,
        out_shardings=None,
        eval_fn: Callable | None = None,
        tracer=None,
        runlog=None,
    ):
        self._build = build_step
        # telemetry sinks (repro.obs); the null defaults make every emit a
        # strict no-op, and hot paths additionally guard on .enabled
        self.tracer = tracer if tracer is not None else trace_lib.NULL
        self.runlog = runlog if runlog is not None else runlog_lib.NULL
        try:
            sig_params = inspect.signature(build_step).parameters.values()
            # only genuinely positional parameters count — a (key, **opts)
            # or keyword-only second arg cannot receive a positional tier
            n_params = sum(
                1 for p in sig_params
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
            )
        except (TypeError, ValueError):  # builtins/partials without signature
            n_params = 1
        #: whether build_step accepts a tier argument (see class docstring)
        self.tiered = n_params >= 2
        #: whether build_step also accepts the rung token (see class docstring)
        self.rung_aware = n_params >= 3
        # The active estimator-tier token (any hashable; the Trainer uses the
        # tier name). Part of the executable cache key exactly like ``rung``.
        # None = the build's own default tier (non-tiered engines stay None).
        self.tier = None
        self._bucket_of = bucket_of or (
            lambda batch: int(jax.tree.leaves(batch)[0].shape[0])
        )
        # Elastic mode: the current ladder-rung token (any hashable; the
        # Trainer uses the rung index). It is part of the executable cache
        # key — AOT executables are sharding-exact, so a state resharded onto
        # a different rung must never dispatch into another rung's program.
        # None (the default) keys every step identically: non-elastic
        # callers see the pure (bucket, signature) cache.
        self.rung = None
        self.donate = donate
        self._in_shardings = in_shardings
        self._out_shardings = out_shardings
        self._jits: dict[tuple, Callable] = {}
        self._compiled: dict[tuple, Callable] = {}
        self._eval_fn = eval_fn
        self._eval_jit = None
        self.stats = EngineStats(donate=donate)

    # -- compile cache -------------------------------------------------------
    def jitted(self, key: int) -> Callable:
        """The jax.jit-wrapped step for bucket ``key`` at the active tier
        (not yet compiled — AOT callers like the dry-run lower/compile it
        themselves)."""
        if self.tier is not None and not self.tiered:
            raise ValueError(
                "engine.tier was set but build_step takes no tier argument; "
                "tier flips on hand-built engines need a (key, tier) build"
            )
        jkey = (key, self.tier, self.rung if self.rung_aware else None)
        if jkey not in self._jits:
            kwargs = {}
            if self._in_shardings is not None:
                kwargs["in_shardings"] = self._in_shardings
            if self._out_shardings is not None:
                kwargs["out_shardings"] = self._out_shardings
            if self.donate:
                kwargs["donate_argnums"] = (0,)
            if self.rung_aware:
                fn = self._build(key, self.tier, self.rung)
            elif self.tiered:
                fn = self._build(key, self.tier)
            else:
                fn = self._build(key)
            self._jits[jkey] = jax.jit(fn, **kwargs)
        return self._jits[jkey]

    def _pinned(self, key: int, state: TrainState) -> Callable:
        """``jitted(key)``, with the state output pinned to the layout of the
        state passed in when that state lives on a mesh and the caller gave
        no ``out_shardings``.  Left free, the compiler may lay an output out
        differently (a replicated parameter comes back sharded like its
        gradient), and the next step's executable, which is sharding-exact,
        would refuse the state it is handed."""
        fn = self.jitted(key)
        if self._out_shardings is not None:
            return fn
        shardings = jax.tree.map(lambda x: x.sharding, state)
        if not any(isinstance(sh, NamedSharding) for sh in jax.tree.leaves(shardings)):
            return fn
        return jax.jit(fn, out_shardings=(shardings, None),
                       donate_argnums=(0,) if self.donate else ())

    def _executable(self, key: int, state: TrainState, batch: PyTree, lr):
        # AOT executables are shape- and sharding-exact, so the cache key
        # carries the full batch signature, the rung, and the estimator tier,
        # not just the bucket: batches agreeing on leading dim but differing
        # in trailing shape / dtype / structure / mesh rung / step program
        # get their own compile instead of dispatching into an incompatible
        # executable.
        sig = (
            key,
            self.rung,
            self.tier,
            jax.tree.structure(batch),
            tuple((leaf.shape[1:], str(leaf.dtype)) for leaf in jax.tree.leaves(batch)),
        )
        if sig in self._compiled:
            self.stats.bucket_hits += 1
            return self._compiled[sig]
        self.stats.bucket_misses += 1
        t0 = time.perf_counter()
        # AOT-compile so the compile count/time is exact, not inferred from
        # jit retrace behaviour.
        with self.tracer.span("compile", scope="train", bucket=key,
                              rung=self.rung, tier=str(self.tier)):
            compiled = self._pinned(key, state).lower(state, batch, lr).compile()
        dt = time.perf_counter() - t0
        if self.runlog.enabled:
            self.runlog.emit("compile", scope="train", what=f"bucket={key}",
                             seconds=dt, bucket=key, rung=self.rung,
                             tier=str(self.tier))
        self.stats.compile_s += dt
        self.stats.compiles += 1
        self.stats.buckets.append(key)
        self.stats.rungs.append(self.rung)
        self.stats.tiers.append(self.tier)
        self._compiled[sig] = compiled
        return compiled

    # -- stepping ------------------------------------------------------------
    def step(
        self, state: TrainState, batch: PyTree, lr
    ) -> tuple[TrainState, dict]:
        """One optimizer step at whatever bucket ``batch`` lands on.

        Donation invalidates the buffers of the *passed-in* state — callers
        must hold only the returned state (the Trainer does).
        """
        key = self._bucket_of(batch)
        lr = jnp.asarray(lr, jnp.float32)
        fn = self._executable(key, state, batch, lr)
        tr = self.tracer
        t0 = time.perf_counter()
        # the disabled path is one attribute load + branch (overhead guard
        # in tests/test_obs.py pins it): no span object, no clock beyond the
        # pre-existing dispatch_wall_s pair, no host transfer
        if tr.enabled:
            with tr.span("dispatch", bucket=key, rung=self.rung,
                         tier=str(self.tier), step_num=self.stats.steps):
                out = fn(state, batch, lr)
        else:
            out = fn(state, batch, lr)
        self.stats.dispatch_wall_s += time.perf_counter() - t0
        self.stats.steps += 1
        return out

    def evaluate(self, params: PyTree, batch: PyTree):
        """(loss, metrics) on a batch — cached jit, params NOT donated."""
        if self._eval_fn is None:
            raise ValueError("engine was built without an eval_fn")
        if self._eval_jit is None:
            self._eval_jit = jax.jit(self._eval_fn)
        return self._eval_jit(params, batch)

    def ensure_eval_fn(self, eval_fn: Callable) -> None:
        """Install ``eval_fn(params, batch) -> (loss, metrics)`` if the engine
        has none — lets the Trainer accept hand-built/injected engines."""
        if self._eval_fn is None:
            self._eval_fn = eval_fn

    def reset_stats(self) -> None:
        self.stats = EngineStats(donate=self.donate)

    # -- constructors --------------------------------------------------------
    @classmethod
    def for_model_fns(
        cls,
        fns: ModelFns,
        optimizer: Optimizer,
        *,
        estimator: str = "moment",
        diversity_on: bool = True,
        dp_size: int = 1,
        donate: bool = True,
        psn_chunk: int | None = None,
        psn_impl: str = "auto",
        psn_interpret: bool | None = None,
    ) -> "StepEngine":
        """Engine over generic ``ModelFns`` (the paper's reference models).

        One bucket = one global batch size; ``num_micro`` is 1, so each batch
        is exactly one SGD step (Algorithm 1's step granularity) and the
        compiled program is arithmetically identical to the classic
        ``value_and_grad`` + update step.

        The build is tier-parameterised: the engine starts on ``estimator``
        and a later ``engine.tier = "gram"`` (a Decision.estimator flip)
        compiles that tier's buckets alongside the old ones — the (bucket,
        rung, tier) cache makes the flip back a hit.
        """
        injit = ("exact", "gram", "moment")

        def build(key: int, tier: str | None = None) -> Callable:
            est = tier if tier is not None else estimator
            track = diversity_on and est in injit
            return step_lib.make_train_step(
                None,
                optimizer,
                num_micro=1,
                dp_size=dp_size,
                diversity_on=track,
                loss_fn=fns.batch_loss,
                estimator=est if track else "moment",
                example_loss=fns.example_loss,
                probe_loss=fns.probe_loss,
                probe_specs=fns.probe_specs,
                psn_chunk=psn_chunk,
                psn_impl=psn_impl,
                psn_interpret=psn_interpret,
            )

        eng = cls(build, donate=donate, eval_fn=eval_fn_for(fns))
        if diversity_on and estimator in injit:
            # name the starting tier so a flip away and back shares the key
            eng.tier = estimator
        return eng

    @classmethod
    def for_lm(
        cls,
        cfg,
        optimizer: Optimizer,
        *,
        micro_batch: int | None = None,
        dp_size: int = 1,
        moe_groups: int = 1,
        diversity_on: bool = True,
        grad_accum_dtype=jnp.float32,
        donate: bool = True,
        in_shardings=None,
        out_shardings=None,
        attn_impl: str | None = None,
    ) -> "StepEngine":
        """Engine over the transformer LM loss (production path).

        One bucket = one ``num_micro`` (accumulation length); the microbatch
        shape is fixed per mesh, so with ``micro_batch`` given the bucket of
        a global batch of B sequences is ``B // micro_batch``.

        ``attn_impl`` overrides ``cfg.attn_impl`` for the training forward
        ("pallas" puts the flash kernel — forward AND recompute backward —
        on the kernels/attention.py lane).
        """
        if attn_impl is not None:
            cfg = cfg.replace(attn_impl=attn_impl)

        def build(num_micro: int, tier: str | None = None) -> Callable:
            return step_lib.make_train_step(
                cfg,
                optimizer,
                num_micro,
                dp_size=dp_size,
                moe_groups=moe_groups,
                diversity_on=diversity_on,
                grad_accum_dtype=grad_accum_dtype,
                **({"estimator": tier} if tier is not None else {}),
            )

        if micro_batch is None:
            # Without a microbatch size the bucket key (num_micro) cannot be
            # derived from a batch: AOT-only use via .jitted(num_micro).
            def bucket_of(batch):
                raise ValueError(
                    "StepEngine.for_lm was built without micro_batch: use "
                    ".jitted(num_micro) directly, or pass micro_batch= to "
                    "enable .step()"
                )
        else:

            def bucket_of(batch):
                b = int(jax.tree.leaves(batch)[0].shape[0])
                if b % micro_batch != 0:
                    # Two shapes must never share a cache key: the per-bucket
                    # executables are AOT-compiled and shape-exact.
                    raise ValueError(
                        f"global batch {b} is not a multiple of micro_batch "
                        f"{micro_batch}; batch sizes must land on the bucket "
                        f"lattice (core/batch_policy.bucket)"
                    )
                return max(b // micro_batch, 1)

        eng = cls(
            build,
            bucket_of=bucket_of,
            donate=donate,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
        )
        if diversity_on:
            # name the default tier (make_train_step's "moment") so a flip
            # away and back lands on the warm key, exactly like for_model_fns
            eng.tier = "moment"
        return eng
