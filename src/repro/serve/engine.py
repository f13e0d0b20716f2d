"""ServeEngine — elastic continuous-batching prefill/decode over a paged KV
block pool.

The serving mirror of the train stack's single path: one engine, a bucketed
``(bucket, rung)`` compile cache, and a ``MeshLadder`` that lets the live
request load drive the device footprint — DiveBatch's rule ("run as wide as
the batch justifies, no wider") applied to inference.  Since PR 6 the cache
side applies the same rule to MEMORY: KV for full-attention layers lives in a
vLLM-style block pool, so the footprint tracks resident tokens instead of
``max_slots * max_seq``.

Pieces:

  * ``Scheduler`` (serve/scheduler.py) — true continuous batching: an
    admission queue, slot free/refill at every step boundary, per-slot
    EOS/max-token retirement.  Admission is gated by the block pool's
    reservation check (worst-case blocks are promised up front, so a live
    request can never strand mid-decode on an exhausted pool).
  * ``ServePolicy`` (serve/policy.py) — the serve-side mirror of
    ``adapt.AdaptationPolicy``: at every step boundary (retire -> policy
    observe -> resize -> admit) the engine snapshots ``ServeSignals``
    (queue depth + per-request age, live/pending, windowed tokens/s, pool
    headroom) and the policy's ``ServeDecision`` sets the admission order,
    caps the slot budget, and tunes the shrink patience.  ``FifoPolicy``
    (the default) reproduces the pre-hook engine token-for-token; applied
    decisions mirror into ``serve_policy`` run-log events.
  * ``BlockPool`` (serve/blocks.py) — host accounting for the device pool:
    free list, refcounts, reservations, chain-hashed prefix registry with
    copy-on-write, LRU-evictable cached prefixes.  The device side is
    ``models/transformer.init_pages``: per full-attention pattern position, a
    flat ``(repeats, num_blocks, block, kv, hd)`` pool sharded by
    ``dist.sharding.cache_pspecs`` (block axis over dp, kv heads over tp).
    Block 0 is the sentinel: inactive decode lanes write there, reads are
    masked by per-slot validity.  Windowed rings and SSM state stay in the
    dense per-slot cache — they are O(1) per slot already.
  * per-request block tables — the engine maps each request's logical
    positions to pool blocks (host ``np`` tables rebuilt per step, sentinel
    elsewhere), so ``decode_step`` reads context through a table gather and
    writes the new token at ``table[pos // block]``.  Tables are keyed by
    request, not slot: a resize compacts cache ROWS, the tables just follow
    the request.
  * chunked prefill — prompts stream through ``prefill_chunk`` in
    block-aligned chunks, compiled per ``(chunk, prior-block bucket, rung)``;
    every pending prompt advances one chunk per boundary, interleaved with
    decode, so a long prompt never stalls the running batch.  With
    ``prefill_chunk=0`` (default) a prompt is one chunk — exactly the old
    whole-prompt schedule, which the rung-golden lane pins token-for-token.
  * prefix sharing — padded prompts chain-hash per block; a request whose
    padded prompt matches a registered chain adopts the blocks (refcounted)
    instead of recomputing them.  A FULL-prompt match replays the cached
    end-of-prompt row state + logits and skips prefill entirely (the
    N-thousand-user shared-system-prompt case costs one prefill); a partial
    match (pure full-attention configs, where the pool holds all the state)
    prefills only the tail chunks.
  * compile cache — decode programs AOT-compiled per ``(bucket, rung)`` with
    the pool shape fixed for the engine lifetime, so paging adds ZERO compile
    keys: ``compiles == len(set(zip(buckets, rungs)))`` still holds.
  * ``ServeStats`` — plus pool metrics: ``peak_blocks`` (peak live blocks —
    the resident-token footprint), ``prefill_chunks``, ``shared_prefill_hits``,
    ``cow_copies``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.adapt.signals import ThroughputWindow
from repro.configs.base import ModelConfig
from repro.core.batch_policy import bucket
from repro.dist.plan import current_plan, use_plan
from repro.dist.sharding import cache_pspecs, shardings_of
from repro.elastic import MeshLadder, place
from repro.models import transformer as tf
from repro.obs import metrics as metrics_lib
from repro.obs import runlog as runlog_lib
from repro.obs import trace as trace_lib
from repro.adapt.signals import Clock
from repro.serve.blocks import BlockPool, chain_keys
from repro.serve.policy import (
    FifoPolicy,
    QueuedRequest,
    ServePolicy,
    ServeSignals,
    make_serve_policy,
)
from repro.serve.scheduler import Admission, Request, Result, Scheduler, slots_for

PyTree = Any

SAMPLERS = ("greedy", "categorical")


def padded_prompt_len(n: int, granule: int) -> int:
    """Smallest pow2 prompt bucket (``granule * 2^i``) holding ``n`` tokens
    — the same lattice snap-up as the slot/batch buckets
    (``core/batch_policy.bucket`` with an off-lattice ``m_min`` snaps UP).

    Prompts are LEFT-padded to their own bucket independently of what they
    are batched with, so a request's padding — and therefore its tokens —
    never depends on its co-scheduled neighbours.  Prefix sharing hashes the
    PADDED stream for the same reason: identical padded streams mean
    identical absolute positions, so shared blocks are bit-compatible."""
    return bucket(max(int(n), 1), max(int(granule), 1), "pow2",
                  m_min=max(int(n), 1))


def _insert_row(cache: PyTree, row: PyTree, j) -> PyTree:
    """Write one slot-geometry row into batch position ``j`` of the cache
    (leaf batch axis: 0 for the per-slot ``len`` vector, 1 after the stacked
    repeats axis for every layer leaf)."""
    return jax.tree.map(
        lambda full, r: jax.lax.dynamic_update_slice_in_dim(
            full, r.astype(full.dtype), j, axis=0 if full.ndim == 1 else 1
        ),
        cache,
        row,
    )


def _gather_rows(cache: PyTree, idx) -> PyTree:
    """Re-index the cache batch axis: ``new[i] = old[idx[i]]`` — one program
    covers compaction (shrink), growth, and any slot permutation."""
    return jax.tree.map(
        lambda x: jnp.take(x, idx, axis=0 if x.ndim == 1 else 1), cache
    )


def _copy_block(pages: PyTree, src, dst) -> PyTree:
    """Device side of copy-on-write: duplicate pool block ``src`` into
    ``dst`` across every paged position (block axis is 1, after repeats)."""
    return jax.tree.map(lambda x: x.at[:, dst].set(x[:, src]), pages)


@dataclasses.dataclass
class _BlockState:
    """Host bookkeeping for one request's slice of the pool."""

    tokens: np.ndarray  # the PADDED prompt (plen,)
    plen: int
    budget: int
    nb_prompt: int  # prompt blocks (plen // block_size)
    total_need: int  # worst-case blocks (prompt + decode budget)
    keys: list  # chain keys of the padded prompt ([] with sharing off)
    table: list[int] = dataclasses.field(default_factory=list)
    reserved: int = 0  # outstanding pool credits
    shared: int = 0  # blocks adopted from the prefix registry
    pos: int = 0  # tokens resident on device (mirror of cache["len"])
    ent: dict | None = None  # full-prompt cache hit staged by the gate


@dataclasses.dataclass
class _PrefillJob:
    """A prompt mid-load: one chunk advances per boundary."""

    rid: int
    off: int  # next chunk's first position (block-aligned)
    row: PyTree  # carried per-request state (len, windowed rings, SSM)
    stepped: bool = False


class ServeStats(metrics_lib.StatsView):
    """Observable serving behaviour (mirrors ``train.engine.EngineStats``).

    ``compiles`` counts decode-step compilations — one per distinct
    ``(bucket, rung)`` pair, so ``compiles == len(set(zip(buckets,
    rungs)))``; ``bucket_hits``/``bucket_misses`` count decode cache
    lookups (one per decode step).  ``prefill_compiles`` counts per-(chunk,
    prior-block bucket, rung) prefill programs, ``aux_compiles`` the
    insert/gather/sample helpers.  ``slot_steps`` is the total decoded lanes
    (capacity summed over steps); ``tokens`` counts tokens actually delivered
    to requests.  ``prefills`` counts requests whose prompt became resident
    (including shared-prefix instant hits); ``prefill_chunks`` counts chunk
    programs actually executed — a fully shared prompt runs zero;
    ``shared_prefill_hits`` counts those instant admissions and
    ``shared_blocks`` the pool blocks adopted instead of recomputed.
    ``pool_blocks``/``peak_blocks`` give the pool capacity and the peak
    LIVE (refcounted) block count — the resident-token footprint that
    replaced the dense ``max_slots * max_seq`` preallocation.
    ``tokens_per_sec`` is the windowed rate (``adapt.signals
    .ThroughputWindow``), not a run-global average.

    Like ``EngineStats``, the scalar fields are emitting views over the
    ``repro.obs.metrics`` registry under a fresh ``serve.engine.<n>``
    namespace; the attribute surface and ``as_dict()`` are unchanged.
    """

    _COUNTERS = (
        "compiles", "bucket_hits", "bucket_misses", "prefill_compiles",
        "aux_compiles", "steps", "slot_steps", "tokens", "prefills",
        "prefill_chunks", "shared_prefill_hits", "shared_blocks",
        "reshards", "resizes",
    )
    _GAUGES = (
        "cow_copies", "pool_blocks", "peak_blocks", "block_size",
        "retired", "compile_s", "tokens_per_sec",
    )

    def __init__(self, donate: bool = True, pool_blocks: int = 0,
                 block_size: int = 0, *,
                 registry: metrics_lib.Registry | None = None):
        self.donate = donate
        self.buckets: list[int] = []
        self.rungs: list = []
        self._init_metrics("serve.engine", registry)
        self.pool_blocks = pool_blocks
        self.block_size = block_size

    def as_dict(self) -> dict:
        d = {f: getattr(self, f) for f in (
            "compiles", "bucket_hits", "bucket_misses", "prefill_compiles",
            "aux_compiles", "steps", "slot_steps", "tokens", "prefills",
            "prefill_chunks", "shared_prefill_hits", "shared_blocks",
            "cow_copies", "pool_blocks", "peak_blocks", "block_size",
            "retired", "reshards", "resizes", "compile_s", "tokens_per_sec",
        )}
        d["donate"] = self.donate
        d["buckets"] = list(self.buckets)
        d["rungs"] = list(self.rungs)
        return d


class ServeEngine:
    """Continuous-batching serving over the model zoo, paged KV cache.

    ``submit``/``step`` is the streaming interface (the benches drive
    arrival traces through it); ``generate(requests)`` is the batch
    convenience wrapper (submit everything, drain, collect).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: PyTree,
        *,
        max_slots: int = 8,
        max_seq: int = 1024,
        sampler: str = "greedy",
        temperature: float = 1.0,
        seed: int = 0,
        slot_granule: int = 1,
        prompt_granule: int = 8,
        elastic: MeshLadder | None = None,
        donate: bool = True,
        shrink_patience: int = 2,
        block_size: int | None = None,
        pool_blocks: int | None = None,
        prefill_chunk: int = 0,
        prefix_sharing: bool = True,
        attn_impl: str | None = None,
        policy: ServePolicy | str | None = None,
        tracer=None,
        runlog=None,
        obs_window: int = 16,
    ):
        tf.refuse_serving(cfg)
        if sampler not in SAMPLERS:
            raise ValueError(f"sampler must be one of {SAMPLERS}, got {sampler!r}")
        # attn_impl="pallas" runs the serving hot loop (paged decode, chunked
        # prefill, full prefill) on the kernels/attention.py lane
        self.cfg = cfg.replace(remat=False) if attn_impl is None else cfg.replace(
            remat=False, attn_impl=attn_impl
        )
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq)
        self.sampler = sampler
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.prompt_granule = int(prompt_granule)
        self.donate = bool(donate)
        self.prefix_sharing = bool(prefix_sharing)
        self.block_size = int(block_size) if block_size else self.prompt_granule
        if self.prompt_granule % self.block_size:
            raise ValueError(
                f"prompt_granule {self.prompt_granule} must be a multiple of "
                f"block_size {self.block_size} (prompts pad to whole blocks)"
            )
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk and self.prefill_chunk % self.block_size:
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} must be a multiple of "
                f"block_size {self.block_size}"
            )
        plan = current_plan()
        if elastic is not None and plan is not None:
            raise ValueError(
                "ServeEngine(elastic=...) under an ambient dist plan is "
                "ambiguous: the ladder owns the sharding plan per rung — "
                "drop the use_plan context (or the elastic ladder)"
            )
        self._elastic = elastic
        self._plan = plan
        self._rung = elastic.rungs[0] if elastic is not None else None
        self.sched = Scheduler(self.max_slots, granule=slot_granule)
        self.params = place(params, self._live_plan)
        self._cache: PyTree | None = None
        self._bucket = 0
        # Grow immediately, shrink only once the smaller target has held for
        # ``shrink_patience`` consecutive boundaries — the serving analogue
        # of adapt.Hysteresis: a retirement followed by an arrival would
        # otherwise bounce the bucket (and with it the ladder rung) straight
        # back, paying a resize+reshard both ways.
        self.shrink_patience = int(shrink_patience)
        self._shrink_streak = 0
        # -- the adaptation policy hook (serve/policy.py) -------------------
        # observe -> decide at every boundary, mirroring the train side's
        # adapt.AdaptationPolicy; FifoPolicy is provably the pre-hook engine
        if policy is None:
            policy = FifoPolicy()
        elif isinstance(policy, str):
            policy = make_serve_policy(policy)
        self.policy = policy
        self._slot_budget: int | None = None  # persists until a decision moves it
        self._adm_order: list[int] | None = None  # this boundary's ordering
        self._sample = self._sampler_fn()
        self._exes: dict[tuple, Any] = {}
        # -- the paged pool -------------------------------------------------
        # Table capacity: the satellite-3 budget fix lets logical positions
        # run past max_seq by the prompt's padding slack (plen - raw), which
        # is < max(granule, max_seq/2) on the pow2 lattice.
        span = self.max_seq + max(self.prompt_granule, self.max_seq // 2)
        self._n_max = -(-span // self.block_size)
        self._paged = tf.paged_positions(self.cfg)
        if pool_blocks is None:
            # pow2 default: worst-case credits for every slot + the sentinel
            # (pow2 also keeps the dp sharding of the block axis even)
            pool_blocks = padded_prompt_len(1 + self.max_slots * self._n_max, 1)
        self.pool = BlockPool(int(pool_blocks), self.block_size)
        self._pages = self._place_cache(
            tf.init_pages(self.cfg, int(pool_blocks), self.block_size)
        )
        # a partial chain match only covers full-attention state (it lives in
        # the pool); configs with rings/SSM share only on full-prompt hits
        self._row_trivial = len(self._paged) == self.cfg.period
        self._req_blocks: dict[int, _BlockState] = {}
        self._jobs: list[_PrefillJob] = []
        self._prompt_cache: collections.OrderedDict = collections.OrderedDict()
        self._prompt_cache_cap = 256
        self.stats = ServeStats(
            donate=self.donate,
            pool_blocks=self.pool.num_blocks,
            block_size=self.block_size,
        )
        self._thru = ThroughputWindow()
        # telemetry sinks (repro.obs); every event of one request carries
        # its rid: submit, admit, prefill_chunk, first_token, retire
        self.tracer = tracer if tracer is not None else trace_lib.NULL
        self.runlog = runlog if runlog is not None else runlog_lib.NULL
        #: emit a ``serve_window`` run-log event every this many decode steps
        self.obs_window = int(obs_window)

    # -- plumbing ------------------------------------------------------------
    @property
    def _live_plan(self):
        return self._rung.plan if self._rung is not None else self._plan

    @property
    def _rung_token(self):
        return self._rung.index if self._rung is not None else None

    @property
    def rung(self):
        """The live elastic ladder rung (None outside elastic mode)."""
        return self._rung

    @property
    def busy(self) -> bool:
        return self.sched.has_work

    def _sampler_fn(self):
        if self.sampler == "greedy":

            def sample(logits, rids, pos):
                return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

            return sample
        base, temp = self.seed, self.temperature

        def sample(logits, rids, pos):
            # per-slot keys derived from (engine seed, request id, position):
            # sampling is deterministic per request, independent of which
            # slot/bucket/neighbours the request happens to be batched with
            def one(lg, rid, p):
                k = jax.random.fold_in(
                    jax.random.fold_in(jax.random.key(base), rid), p
                )
                return jax.random.categorical(k, lg / temp)

            return jax.vmap(one)(logits[:, -1, :], rids, pos).astype(jnp.int32)

        return sample

    def _decode_fn(self):
        cfg, sample = self.cfg, self._sample

        def fn(params, cache, pages, tables, toks, rids):
            logits, cache, pages = tf.decode_step(
                cfg, params, cache, toks, pages=pages, tables=tables
            )
            return sample(logits, rids, cache["len"]), cache, pages

        return fn

    def _chunk_fn(self):
        cfg, sample = self.cfg, self._sample

        def fn(params, pages, row, toks, rid, ptab, wtab, off):
            logits, row, pages = tf.prefill_chunk(
                cfg, params, row, pages, {"tokens": toks}, off, ptab, wtab
            )
            # only the FINAL chunk's token is consumed (row["len"] == plen
            # there); intermediate chunk tokens are discarded by the caller
            tok = sample(logits, rid[None], row["len"])
            return tok, logits, row, pages

        return fn

    def _cache_shardings(self, tree):
        plan = self._live_plan
        if plan is None:
            return None
        return shardings_of(cache_pspecs(tree, plan), plan)

    def _place_cache(self, cache: PyTree) -> PyTree:
        """KV/SSM cache onto the live plan via ``dist.sharding.cache_pspecs``
        (batch rows / pool blocks over dp, kv-heads over tp; plan-free =
        leave as is)."""
        sh = self._cache_shardings(cache)
        return cache if sh is None else jax.device_put(cache, sh)

    def _exe(self, key, fn, args, *, donate=(), out_pin=None, kind="aux"):
        """AOT-compiled program for ``key`` (mirrors StepEngine._executable:
        exact compile accounting, sharding-exact executables).  ``fn`` and
        ``out_pin`` are zero-arg thunks so a cache hit — the per-step hot
        path — pays one dict lookup, not a retrace/sharding-inference;
        ``out_pin`` pins cache outputs to the canonical cache_pspecs
        shardings so every program on a rung agrees on the cache layout."""
        if key in self._exes:
            if kind == "decode":
                self.stats.bucket_hits += 1
            return self._exes[key]
        if kind == "decode":
            self.stats.bucket_misses += 1
        # On an elastic rung the ladder owns the plan: trace under it, the
        # shape pass of ``out_pin`` included (JAX caches a trace without the
        # plan in its key), so the model's plan-aware calls
        # (dist.plan.shard_local around each Pallas kernel, which the
        # compiler cannot partition) see the rung's mesh.
        plan_ctx = (use_plan(self._rung.plan) if self._rung is not None
                    else contextlib.nullcontext())
        with plan_ctx, warnings.catch_warnings():
            fn = fn()
            kwargs = {}
            if donate and self.donate:
                kwargs["donate_argnums"] = donate
            if out_pin is not None and self._live_plan is not None:
                pin = out_pin()
                if pin is not None:
                    kwargs["out_shardings"] = pin
            # a grow-gather's donated (smaller) cache cannot alias the larger
            # output — partial donation is expected there, not a leak
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            t0 = time.perf_counter()
            with self.tracer.span("compile", scope="serve", kind=kind,
                                  key=str(key)):
                exe = jax.jit(fn, **kwargs).lower(*args).compile()
        dt = time.perf_counter() - t0
        if self.runlog.enabled:
            self.runlog.emit("compile", scope="serve", what=str(key),
                             seconds=dt, exe_kind=kind, rung=self._rung_token)
        self.stats.compile_s += dt
        if kind == "decode":
            self.stats.compiles += 1
            self.stats.buckets.append(self._bucket)
            self.stats.rungs.append(self._rung_token)
        elif kind == "prefill":
            self.stats.prefill_compiles += 1
        else:
            self.stats.aux_compiles += 1
        self._exes[key] = exe
        return exe

    # -- elastic -------------------------------------------------------------
    def _ensure_rung(self) -> None:
        """Move params + cache + pool + in-flight prefill state onto the
        ladder rung for the live slot count (no-op off-ladder or on an
        unchanged rung)."""
        if self._elastic is None:
            return
        rung = self._elastic.rung_for_batch(max(self._bucket, 1))
        if rung.index == self._rung.index:
            return
        src = self._rung
        with self.tracer.span("reshard", scope="serve", src=src.index,
                              dst=rung.index, dp=rung.dp):
            self._rung = rung
            self.params = place(self.params, rung.plan)
            if self._cache is not None:
                self._cache = self._place_cache(self._cache)
            self._pages = self._place_cache(self._pages)
            for job in self._jobs:
                job.row = self._place_cache(job.row)
            for ent in self._prompt_cache.values():
                ent["row"] = self._place_cache(ent["row"])
        if self.runlog.enabled:
            self.runlog.emit("reshard", scope="serve", src=src.index,
                             dst=rung.index, dp=rung.dp,
                             step=self.stats.steps)
        self.stats.reshards += 1

    def _resize(self, target: int) -> None:
        """Track the scheduler's pow2 slot capacity: grow/shrink the batched
        per-slot cache (compacting live rows via the scheduler's gather map
        — the POOL never resizes, tables just follow their requests), then
        follow with the rung transition."""
        if target == self._bucket:
            return
        idx = self.sched.resize(target)
        old = self._bucket
        self._bucket = target
        if target == 0:
            self._cache = None  # the pool (and its cached prefixes) persists
            return
        self.stats.resizes += 1
        if self._cache is None:
            self._ensure_rung()
            cache = tf.init_cache(self.cfg, target, self.max_seq, skip=self._paged)
            cache["len"] = jnp.zeros((target,), jnp.int32)  # per-slot timeline
            self._cache = self._place_cache(cache)
            return
        idx_arr = np.asarray(idx, np.int32)
        exe = self._exe(
            ("gather", old, target, self._rung_token), lambda: _gather_rows,
            (self._cache, idx_arr), donate=(0,),
            out_pin=lambda: self._cache_shardings(
                jax.eval_shape(_gather_rows, self._cache, idx_arr)
            ),
        )
        self._cache = exe(self._cache, idx_arr)
        self._ensure_rung()

    # -- admission -----------------------------------------------------------
    def submit(self, request: Request) -> int:
        """Queue a request; returns its id (``result(rid)`` after drain)."""
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        plen = padded_prompt_len(len(prompt), self.prompt_granule)
        if plen > self.max_seq:
            raise ValueError(
                f"prompt of {len(prompt)} tokens pads to {plen} > max_seq "
                f"{self.max_seq}"
            )
        # headroom from the TRUE prompt length: with block tables the pad
        # columns cost table entries, not budget — a request near max_seq
        # keeps its full max_new_tokens (positions may pass max_seq by the
        # padding slack; _n_max sizes the tables for exactly that)
        budget = min(int(request.max_new_tokens), self.max_seq - len(prompt) + 1)
        padded = np.zeros(plen, np.int32)
        if len(prompt):
            padded[plen - len(prompt):] = prompt  # left-pad
        nb_prompt = plen // self.block_size
        total_need = nb_prompt + -(-(budget - 1) // self.block_size)
        if total_need > self.pool.num_blocks - 1:
            raise ValueError(
                f"request needs {total_need} pool blocks but the pool holds "
                f"{self.pool.num_blocks - 1}; raise pool_blocks"
            )
        rid = self.sched.submit(request, budget=budget)
        if self.tracer.enabled:
            self.tracer.instant("submit", rid=rid, prompt_len=len(prompt))
        self._req_blocks[rid] = _BlockState(
            tokens=padded, plen=plen, budget=budget, nb_prompt=nb_prompt,
            total_need=total_need,
            keys=chain_keys(padded, self.block_size) if self.prefix_sharing else [],
        )
        return rid

    def _shared_prefix(self, bs: _BlockState):
        """(adoptable prefix block ids, full-prompt cache entry or None).

        A full-chain match alone cannot emit token 1 (no logits cached in the
        pool), so it is only an instant admission when the prompt cache still
        holds the end-of-prompt row + logits AND the registry still maps the
        whole chain to the entry's blocks; otherwise fall back to a partial
        match capped at nb_prompt - 1 — valid only for pure full-attention
        configs (ring/SSM state is not in the pool)."""
        if not self.prefix_sharing or not bs.keys:
            return [], None
        ent = self._prompt_cache.get(bs.keys[-1])
        if ent is not None:
            ids = self.pool.match(bs.keys)
            if len(ids) == bs.nb_prompt and ids == ent["ids"]:
                self._prompt_cache.move_to_end(bs.keys[-1])
                return ids, ent
            del self._prompt_cache[bs.keys[-1]]  # stale: blocks evicted
        if not self._row_trivial:
            return [], None
        return self.pool.match(bs.keys[:bs.nb_prompt - 1]), None

    def _gate(self, rid: int, request: Request) -> bool:
        """Admission gate AND claim: can the pool cover this request's worst
        case?  A passing gate immediately adopts the shared prefix, reserves
        the rest, and allocates the prompt blocks — the claim must land
        before the scheduler gates the NEXT queue head in the same pass, or
        two admissions would both be judged against the unclaimed pool.
        (``Scheduler.admit`` guarantees a passing gate IS admitted, so a
        claim is never orphaned.)"""
        bs = self._req_blocks[rid]
        ids, ent = self._shared_prefix(bs)
        if not self.pool.feasible(ids, bs.total_need):
            return False
        for b in ids:
            self.pool.retain(b)
        self.pool.reserve(bs.total_need - len(ids))
        bs.reserved = bs.total_need - len(ids)
        bs.shared = len(ids)
        bs.table = list(ids)
        while len(bs.table) < bs.nb_prompt:
            bs.table.append(self.pool.alloc(reserved=True))
            bs.reserved -= 1
        bs.ent = ent
        self.stats.shared_blocks += len(ids)
        self.stats.peak_blocks = self.pool.peak_live
        return True

    def _begin(self, adm: Admission) -> None:
        """Start an admitted request (blocks were claimed by ``_gate``):
        either replay a full-prompt cache hit or start a chunked prefill
        job."""
        bs = self._req_blocks[adm.rid]
        ent, bs.ent = bs.ent, None
        if self.runlog.enabled:
            self.runlog.emit("serve_admit", rid=adm.rid, prompt_len=bs.plen,
                             budget=bs.budget, shared=bs.shared,
                             full_hit=ent is not None)
        with self.tracer.span("admit", rid=adm.rid, prompt_len=bs.plen,
                              shared=bs.shared):
            if ent is not None:
                self._admit_shared(adm, bs, ent)
            else:
                self._jobs.append(_PrefillJob(
                    rid=adm.rid, off=bs.shared * self.block_size,
                    row=self._fresh_row(bs.shared * self.block_size),
                ))

    def _fresh_row(self, off: int) -> PyTree:
        """Zeroed per-request prefill carry, starting at position ``off``
        (> 0 when a shared prefix was adopted)."""
        row = tf.init_cache(self.cfg, 1, self.max_seq, skip=self._paged)
        row["len"] = jnp.full((1,), off, jnp.int32)
        return self._place_cache(row)

    def _admit_shared(self, adm: Admission, bs: _BlockState, ent: dict) -> None:
        """Full-prompt cache hit: the prompt is already resident — replay the
        cached end-of-prompt logits through the sampler (keyed by THIS
        request's rid, so categorical streams stay per-request) and insert
        the cached row.  Zero prefill compute."""
        rid = np.asarray(adm.rid, np.int32)
        pos = np.full((1,), bs.plen, np.int32)
        exe = self._exe(
            ("sample", self._rung_token), lambda: self._sample,
            (ent["logits"], rid[None], pos),
        )
        tok = exe(ent["logits"], rid[None], pos)
        self._insert(adm.slot, ent["row"])
        bs.pos = bs.plen
        self.stats.prefills += 1
        self.stats.shared_prefill_hits += 1
        self._count_token(1)
        done = self.sched.record(adm.slot, int(jax.device_get(tok)[0]))
        if self.tracer.enabled:
            self.tracer.instant("first_token", rid=adm.rid)
        if done:
            self._release(adm.rid)

    def _insert(self, slot: int, row: PyTree) -> None:
        j = np.asarray(slot, np.int32)
        iexe = self._exe(
            ("insert", self._bucket, self._rung_token), lambda: _insert_row,
            (self._cache, row, j), donate=(0,),
            out_pin=lambda: self._cache_shardings(self._cache),
        )
        self._cache = iexe(self._cache, row, j)

    def _count_token(self, n: int) -> None:
        self.stats.tokens += n
        self._thru.add(float(n))
        rate = self._thru.rate()
        if rate is not None:
            self.stats.tokens_per_sec = rate

    # -- the policy boundary -------------------------------------------------
    def _signals(self) -> ServeSignals:
        """Snapshot the queue/slot/pool state for ``policy.observe`` (host
        state only — zero device transfers)."""
        sch = self.sched
        now = sch.clock()
        return ServeSignals(
            queue_depth=sch.pending,
            live=sch.live,
            capacity=sch.capacity,
            tokens_per_sec=self._thru.rate(now=now),
            free_blocks=self.pool.free,
            reserved_blocks=self.pool.reserved,
            queued=tuple(
                QueuedRequest(rid=rid, tenant=req.tenant,
                              priority=req.priority,
                              age=max(now - t, 0.0),
                              prompt_len=len(req.prompt))
                for rid, req, t in sch.queued()
            ),
            step=self.stats.steps,
        )

    def _observe_policy(self) -> None:
        """The boundary's policy phase (retire -> OBSERVE -> resize ->
        admit): build signals, let the policy decide, and apply — the
        admission ordering for this boundary, the persistent slot-budget
        cap, and the shrink patience.  Applied decisions that change
        anything mirror into a ``serve_policy`` run-log event; an ordering
        equal to FIFO is the identity and takes the legacy admit path."""
        self._adm_order = None
        sig = self._signals()
        clock = Clock(epoch=0, step=self.stats.steps, boundary="tick")
        if self.tracer.enabled:
            with self.tracer.span("observe", scope="serve",
                                  step_num=self.stats.steps):
                d = self.policy.observe(sig, clock)
        else:
            d = self.policy.observe(sig, clock)
        if d is None:
            return
        reordered = False
        if d.order is not None:
            order = tuple(d.order)
            if order != tuple(q.rid for q in sig.queued):
                self._adm_order = list(order)
                reordered = True
        changed = reordered
        if d.slot_budget is not None and int(d.slot_budget) != self._slot_budget:
            self._slot_budget = int(d.slot_budget)
            changed = True
        if (d.shrink_patience is not None
                and int(d.shrink_patience) != self.shrink_patience):
            self.shrink_patience = int(d.shrink_patience)
            changed = True
        if changed and self.runlog.enabled:
            self.runlog.emit(
                "serve_policy", step=self.stats.steps,
                reason=d.reason or type(self.policy).__name__,
                reordered=reordered, slot_budget=self._slot_budget,
                shrink_patience=self.shrink_patience,
                queue_depth=sig.queue_depth,
            )

    def _target_slots(self) -> int:
        """The scheduler's pow2 slot target, clamped under the policy's
        slot budget.  The effective budget is at least ``max(live, 1)``:
        a budget can throttle admission but never evicts live requests or
        stalls the drain."""
        target = self.sched.target_slots()
        if self._slot_budget is None:
            return target
        cap = max(self._slot_budget, self.sched.live, 1)
        need = min(self.sched.live + self.sched.pending, cap)
        return min(target, slots_for(need, self.sched.granule, self.max_slots))

    # -- chunked prefill -----------------------------------------------------
    def _run_chunk(self, job: _PrefillJob) -> None:
        """Advance one prompt by one block-aligned chunk.  The prior-context
        table is padded to a pow2 block count so the compile key is
        ``(chunk, prior bucket, rung)`` — O(log max_seq) programs, not one
        per offset."""
        bs = self._req_blocks[job.rid]
        c = bs.plen - job.off
        if self.prefill_chunk:
            c = min(c, self.prefill_chunk)
        toks = bs.tokens[None, job.off:job.off + c]
        nbp_real = job.off // self.block_size
        nbp = padded_prompt_len(nbp_real, 1) if nbp_real else 0
        ptab = np.zeros((nbp,), np.int32)
        ptab[:nbp_real] = bs.table[:nbp_real]
        wtab = np.asarray(
            bs.table[nbp_real:(job.off + c) // self.block_size], np.int32
        )
        rid = np.asarray(job.rid, np.int32)
        off = np.int32(job.off)
        fn = self._chunk_fn()
        args = (self.params, self._pages, job.row, toks, rid, ptab, wtab, off)
        exe = self._exe(
            ("pfchunk", c, nbp, self._rung_token), lambda: fn, args,
            donate=(1, 2),
            out_pin=lambda: (
                None, None,
                self._cache_shardings(jax.eval_shape(fn, *args)[2]),
                self._cache_shardings(self._pages),
            ),
            kind="prefill",
        )
        tr = self.tracer
        if tr.enabled:
            with tr.span("prefill_chunk", rid=job.rid, off=job.off, chunk=c,
                         rung=self._rung_token):
                tok, logits, job.row, self._pages = exe(*args)
        else:
            tok, logits, job.row, self._pages = exe(*args)
        job.off += c
        self.stats.prefill_chunks += 1
        if job.off == bs.plen:
            self._finish_job(job, tok, logits)

    def _finish_job(self, job: _PrefillJob, tok, logits) -> None:
        """Final chunk done: register the prompt chain, cache the
        end-of-prompt state for future full-prompt hits, insert the row, and
        record token 1."""
        bs = self._req_blocks[job.rid]
        self._jobs.remove(job)
        bs.pos = bs.plen
        # the device wait for the prompt's last chunk (its logits come with it)
        tr = self.tracer
        if tr.enabled:
            with tr.span("prefill_read", rid=job.rid):
                first = int(jax.device_get(tok)[0])
        else:
            first = int(jax.device_get(tok)[0])
        if self.prefix_sharing and bs.keys:
            for key, bid in zip(bs.keys, bs.table[:bs.nb_prompt]):
                self.pool.register(key, bid)  # first writer wins
            ids = self.pool.match(bs.keys)
            if len(ids) == bs.nb_prompt:
                self._prompt_cache[bs.keys[-1]] = {
                    "ids": ids,
                    "row": job.row,
                    # host copy: rung-independent, tiny (1 x vocab)
                    "logits": jax.device_get(logits),
                }
                while len(self._prompt_cache) > self._prompt_cache_cap:
                    self._prompt_cache.popitem(last=False)
        slot = self.sched.slot_of(job.rid)
        self._insert(slot, job.row)
        self.stats.prefills += 1
        self._count_token(1)
        done = self.sched.record(slot, first)
        if tr.enabled:
            tr.instant("first_token", rid=job.rid)
        if done:
            self._release(job.rid)

    def _prefill_work(self) -> None:
        """Admissions + one chunk per pending prompt, repeated while instant
        retirements (EOS/budget at token 1) keep freeing slots.  Each job
        advances at most one chunk per boundary — long prompts interleave
        with decode instead of stalling it."""
        for job in self._jobs:
            job.stepped = False
        while True:
            adms = self.sched.admit(gate=self._gate, order=self._adm_order)
            for adm in adms:
                self._begin(adm)
            pending = [j for j in self._jobs if not j.stepped]
            if not pending:
                if not adms:
                    return
                continue
            for job in pending:
                job.stepped = True
                self._run_chunk(job)

    # -- block tables --------------------------------------------------------
    def _release(self, rid: int) -> None:
        """Retirement: drop the request's block refs (registered prompt
        blocks fall back to the evictable prefix cache) and return unspent
        reservation credits."""
        bs = self._req_blocks.pop(rid)
        if self.tracer.enabled:
            self.tracer.instant("retire", rid=rid, pos=bs.pos)
        for b in bs.table:
            self.pool.release(b)
        if bs.reserved:
            self.pool.unreserve(bs.reserved)
            bs.reserved = 0
        if self.runlog.enabled:
            self.runlog.emit("serve_retire", rid=rid, pos=bs.pos,
                             live_blocks=self.pool.live)
        self.stats.peak_blocks = self.pool.peak_live
        self.stats.cow_copies = self.pool.cow_copies

    def _decode_tables(self, running) -> np.ndarray:
        """(bucket, n_max) int32 block tables for this decode step.  Rows of
        non-running lanes stay all-sentinel, so their (garbage) writes land
        in block 0.  Extends each running request's table for the token about
        to be written, spending reserved credits — and copy-on-write guards
        the (unreachable by construction: prompts pad to whole blocks) case
        of a shared write block."""
        arr = np.zeros((self._bucket, self._n_max), np.int32)
        for slot, rid in running:
            bs = self._req_blocks[rid]
            wi = bs.pos // self.block_size
            while len(bs.table) <= wi:
                bs.table.append(self.pool.alloc(reserved=True))
                bs.reserved -= 1
            if not self.pool.writable(bs.table[wi]):
                new = self.pool.cow(bs.table[wi])
                src, dst = np.int32(bs.table[wi]), np.int32(new)
                cexe = self._exe(
                    ("cow", self._rung_token), lambda: _copy_block,
                    (self._pages, src, dst), donate=(0,),
                    out_pin=lambda: self._cache_shardings(self._pages),
                )
                self._pages = cexe(self._pages, src, dst)
                bs.table[wi] = new
                self.stats.cow_copies = self.pool.cow_copies
            arr[slot, :len(bs.table)] = bs.table
        self.stats.peak_blocks = self.pool.peak_live
        return arr

    # -- the serving step ----------------------------------------------------
    def step(self) -> bool:
        """One boundary (retire happened in the previous step's records ->
        policy observe -> resize -> reshard -> admit/prefill-chunks) plus
        one decode step over the slot table.  Returns False once fully
        drained."""
        if not self.sched.has_work:
            # a drained engine starts the next trace fresh: a stale shrink
            # streak would defeat shrink_patience on its first dip
            self._shrink_streak = 0
            return False
        tr = self.tracer
        # disabled path: one attribute load + branch, no clock read
        if tr.enabled:
            chunks = self.stats.prefill_chunks
            with tr.span("serve_step", step_num=self.stats.steps) as span:
                live = self._step()
                span.set(bucket=self._bucket, live=live,
                         prefill_chunks=self.stats.prefill_chunks - chunks)
        else:
            self._step()
        return True

    def _step(self) -> int:
        """The body of :meth:`step`; returns the number of lanes decoded."""
        sch = self.sched
        self._observe_policy()
        target = self._target_slots()
        if 0 < target < self._bucket:
            self._shrink_streak += 1
            if self._shrink_streak <= self.shrink_patience:
                target = self._bucket  # ride out a transient dip
        else:
            self._shrink_streak = 0
        if target != self._bucket:
            self._shrink_streak = 0
        self._resize(target)
        self._prefill_work()
        self.stats.retired = sch.retired  # prefill-instant retirements count
        running = sch.running_slots()
        if not running:  # nothing decoding (drained, or all mid-prefill)
            return 0
        toks = sch.next_tokens()[:, None]
        rids = sch.slot_rids()
        tables = self._decode_tables(running)
        exe = self._exe(
            ("decode", self._bucket, self._rung_token), self._decode_fn,
            (self.params, self._cache, self._pages, tables, toks, rids),
            donate=(1, 2),
            out_pin=lambda: (
                None,
                self._cache_shardings(self._cache),
                self._cache_shardings(self._pages),
            ),
            kind="decode",
        )
        tr = self.tracer
        # the step's one host transfer, a (B,) vector, is also its one wait
        # for the device: the read drains the queue
        if tr.enabled:
            with tr.span("decode", bucket=self._bucket, rung=self._rung_token,
                         live=len(running)):
                nxt, self._cache, self._pages = exe(
                    self.params, self._cache, self._pages, tables, toks, rids
                )
            with tr.span("token_read"):
                nxt = jax.device_get(nxt)
        else:
            nxt, self._cache, self._pages = exe(
                self.params, self._cache, self._pages, tables, toks, rids
            )
            nxt = jax.device_get(nxt)
        self.stats.steps += 1
        self.stats.slot_steps += self._bucket
        for slot, rid in running:
            self._req_blocks[rid].pos += 1
            if sch.record(slot, int(nxt[slot])):
                self._release(rid)
        self._count_token(len(running))
        self.stats.retired = sch.retired
        if (self.runlog.enabled and self.obs_window
                and self.stats.steps % self.obs_window == 0):
            self.runlog.emit(
                "serve_window", step=self.stats.steps, tokens=self.stats.tokens,
                tokens_per_sec=self.stats.tokens_per_sec, live=len(running),
                live_blocks=self.pool.live, bucket=self._bucket,
                rung=self._rung_token,
            )
        return len(running)

    def drain(self) -> None:
        while self.step():
            pass
        self.pool.check()  # drained: conservation + zero leaked blocks

    def result(self, rid: int) -> Result:
        return self.sched.result(rid)

    def generate(self, requests: list[Request]) -> list[Result]:
        """Submit, drain, and collect — results in request order."""
        rids = [self.submit(r) for r in requests]
        self.drain()
        return [self.sched.result(rid) for rid in rids]
