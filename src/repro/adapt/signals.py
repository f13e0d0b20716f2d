"""Training signals for adaptation policies.

``Signals`` is the record every :class:`~repro.adapt.policy.AdaptationPolicy`
observes; ``Clock`` says *when* it is observing (epoch end, every-k-steps
tick, or an external event such as a supervisor Watchdog flag).

The device-side inputs all come from the ``DiversityState`` accumulators the
``StepEngine`` already populates in-jit on every step (``grad_sum``,
``sq_norm_sum``, ``mb_count``, ``sample_count``): the diversity estimate,
the gradient-noise-scale proxy, and the sample count are computed in ONE
cached jit that returns a stacked scalar vector, so a boundary costs at most
one extra device->host transfer on top of the per-step loss (the epoch
boundary's reset of the accumulators rides in the same program).

Gradient-noise scale (McCandlish et al. 2018, "An Empirical Model of
Large-Batch Training"): ``B_noise = tr(Sigma) / ||mu||^2`` where ``Sigma``
is the per-sample gradient covariance and ``mu`` the true gradient.  The
same unbiased small-batch/big-batch moment inversion that powers the
``moment`` diversity tier recovers both quantities from the accumulators —
``E||g||^2`` (small-batch norms) and ``||grad_sum||^2`` (the big-batch
norm) — with zero additional per-step work.  This is the signal the
Sievert-2021 / AdAdaGrad-style :class:`~repro.adapt.policy.GradNoisePolicy`
family adapts on, at sub-epoch granularity.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as trace_lib
from repro.utils import pytree as ptu

EPS = 1e-20

#: boundary kinds a Clock can carry
BOUNDARIES = ("epoch", "tick", "event")


@dataclasses.dataclass(frozen=True)
class Clock:
    """When an observation happens.

    epoch     the epoch the boundary belongs to (the one just finishing for
              ``boundary='epoch'``; the running one for ticks/events).
    step      the global optimizer-step count at the boundary (host-side
              counter; no device sync).
    boundary  'epoch' | 'tick' | 'event'.
    """

    epoch: int
    step: int
    boundary: str = "epoch"

    def __post_init__(self):
        if self.boundary not in BOUNDARIES:
            raise ValueError(
                f"unknown boundary {self.boundary!r}; expected one of {BOUNDARIES}"
            )


@dataclasses.dataclass(frozen=True)
class Signals:
    """What a policy observes at a boundary.  ``None`` = not measured.

    diversity   Delta_hat over the accumulation window (DiveBatch's signal).
    gns         gradient-noise-scale proxy tr(Sigma)/||mu||^2 over the same
                window (GradNoisePolicy's signal).
    loss        most recent per-step mean loss (already host-side).
    throughput  steps/sec over the trailing ThroughputWindow (host-side,
                free; falls back to the global dispatch average before the
                first window fills).
    batch_size  the live global batch size.
    samples     samples accumulated since the last reset (device counter,
                rides in the same transfer as diversity/gns).
    event       name of the external event for ``boundary='event'``.
    diversity_bound  Yin et al.'s batch-size cap ``n * Delta_hat`` over the
                same window (Theorem 3 of "Gradient Diversity: a Key
                Ingredient for Scalable Distributed Learning": speedup is
                provable only up to a batch of n*diversity).  Decoded off
                the same accumulators and stacked into the SAME transfer as
                diversity/gns — no extra device->host read.  The
                ``BoundedRung`` combinator clamps decisions under it.
    """

    diversity: float | None = None
    gns: float | None = None
    loss: float | None = None
    throughput: float | None = None
    batch_size: int = 0
    samples: float = 0.0
    event: str | None = None
    diversity_bound: float | None = None


class ThroughputWindow:
    """Sliding-window rate estimator: events/second over a trailing window.

    ``Signals.throughput`` used to carry the engine's *global* dispatch
    average, which dilutes a straggler or a hot streak over the whole run;
    a policy (or the supervisor Watchdog) reacting to throughput needs the
    recent rate.  ``add(n)`` records ``n`` events now; ``rate()`` is events
    per second over the last ``window_s`` seconds — or over the elapsed time
    so far when the window is not yet full, so early reads are unbiased
    rather than deflated.  ``repro.serve`` reuses the same estimator for
    ``ServeStats.tokens_per_sec`` (events = emitted tokens).

    The clock is injectable (``clock=`` or per-call ``now=``) so the window
    math is unit-testable without sleeping.
    """

    def __init__(self, window_s: float = 10.0,
                 clock: Callable[[], float] = time.monotonic):
        if window_s <= 0:
            raise ValueError(f"window_s must be > 0, got {window_s}")
        self.window_s = float(window_s)
        self._clock = clock
        self._samples: collections.deque[tuple[float, float]] = collections.deque()
        self._start: float | None = None

    def _evict(self, now: float) -> None:
        # strict <: the trailing window is the CLOSED interval
        # [now - window_s, now] — its length is exactly the window_s the
        # denominator charges, so a sample exactly window_s old still
        # counts (the old <= dropped it while still dividing by the full
        # window, deflating the rate at the boundary)
        edge = now - self.window_s
        while self._samples and self._samples[0][0] < edge:
            self._samples.popleft()

    def add(self, n: float = 1.0, now: float | None = None) -> None:
        """Record ``n`` events at ``now`` (defaults to the injected clock)."""
        now = self._clock() if now is None else float(now)
        if self._start is None:
            self._start = now
        self._samples.append((now, float(n)))
        self._evict(now)

    def rate(self, now: float | None = None) -> float | None:
        """Events/second over the trailing window; None before any event.

        The denominator is ``min(window_s, now - first_event_time)`` — a
        window that has only been filling for 2 of its 10 seconds divides by
        2, not 10.  A burst whose events all landed at a single instant has
        no measurable span: the rate charges the full window instead — the
        conservative lower bound — so recorded events always yield a finite,
        non-None rate (the old code returned None as if nothing happened).
        """
        if self._start is None:
            return None
        now = self._clock() if now is None else float(now)
        self._evict(now)
        count = sum(n for _, n in self._samples)
        span = min(self.window_s, now - self._start)
        if span <= 0.0:
            return count / self.window_s
        return count / span


def gns_from_accumulators(div_state: Any, estimator: str = "moment") -> jax.Array:
    """tr(Sigma)/||mu||^2 from the DiversityState accumulators (jit-safe).

    Uses the same moment inversion as ``diversity.diversity_moment``: with
    per-window statistics ``Q`` (sum of small-batch squared norms, batch size
    ``m`` = 1 for the exact/gram tiers, the microbatch size for moment) and
    ``R = ||grad_sum||^2``,

        M  = (R - Q) / (n (n - m))      ~ ||mu||^2        (clamped >= 0)
        E2 = Q/n - (m - 1) M            ~ E||g||^2        (clamped >= eps)
        tr(Sigma) = E2 - M

    Degenerate windows (single small batch, or empty accumulators) return 0.
    """
    n = jnp.maximum(div_state.sample_count, 1.0)
    if estimator in ("exact", "gram"):
        m = jnp.float32(1.0)
    else:
        m = n / jnp.maximum(div_state.mb_count, 1.0)
    Q = div_state.sq_norm_sum
    R = ptu.tree_sq_norm(div_state.grad_sum)
    M = jnp.maximum((R - Q) / jnp.maximum(n * (n - m), EPS), 0.0)
    E2 = jnp.maximum(Q / n - (m - 1.0) * M, EPS)
    tr_sigma = jnp.maximum(E2 - M, 0.0)
    gns = tr_sigma / jnp.maximum(M, EPS)
    degenerate = jnp.logical_or(n - m < 0.5, R < EPS)
    return jnp.where(degenerate, 0.0, gns)


@functools.lru_cache(maxsize=None)
def _read_jit(estimator: str):
    # deferred import: repro.core's __init__ pulls the controller shim, which
    # reaches back into repro.adapt — module-level would be a cycle
    from repro.core import diversity

    def read(div_state):
        est = diversity.estimate(div_state, estimator)
        return jnp.stack(
            [
                est,
                gns_from_accumulators(div_state, estimator),
                div_state.sample_count,
                # Yin et al.'s batch cap n * Delta_hat, off the same decode
                div_state.sample_count * est,
            ]
        )

    return jax.jit(read)


def read_signals(
    state: Any,
    estimator: str = "moment",
    *,
    reset: bool,
    batch_size: int = 0,
    loss: float | None = None,
    throughput: float | None = None,
    event: str | None = None,
    tracer=trace_lib.NULL,
) -> tuple[Signals, Any]:
    """Read boundary signals off a ``TrainState``'s diversity accumulators.

    Returns ``(signals, state)``; with ``reset=True`` the returned state has
    freshly-zeroed accumulators (the epoch-boundary semantics), with
    ``reset=False`` the state is unchanged (mid-epoch ticks observe the
    running window).  Exactly ONE device->host transfer regardless of how
    many scalars are read (they come back stacked).  ``tracer`` records a
    ``read_signals`` span holding ``signals_reset`` (the reset's dispatch)
    and ``signals_transfer`` (the read, which waits for the device).
    """
    from repro.core import diversity  # deferred: see _read_jit

    with tracer.span("read_signals", reset=reset):
        scalars = _read_jit(estimator)(state.div_state)
        if reset:
            # eager, so the zeroed accumulators keep the state's sharding
            with tracer.span("signals_reset"):
                state = state._replace(
                    div_state=diversity.reset_state(state.div_state))
        with tracer.span("signals_transfer"):
            vals = np.asarray(scalars)  # the single host transfer
    sig = Signals(
        diversity=float(vals[0]),
        gns=float(vals[1]),
        samples=float(vals[2]),
        loss=loss,
        throughput=throughput,
        batch_size=int(batch_size),
        event=event,
        diversity_bound=float(vals[3]),
    )
    return sig, state
