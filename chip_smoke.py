#!/usr/bin/env python3
"""Bring-up check on a TPU: adaptive LM training and paged serving at the
full widths of Yi-6B (d_model 4096, 32 query and 4 KV heads, d_ff 11008,
vocab 64000, bf16), on random weights made from a fixed seed.

    python chip_smoke.py               # one chip: train, then serve
    python chip_smoke.py --four-chips  # four chips: sharded training and
                                       # elastic serving, each against its
                                       # one-chip run

Phases, in order, in one process (a chip belongs to one process):

  device  JAX must report a TPU; anything else exits non-zero before any
          phase runs.  There is no CPU fallback.
  train   ``StepEngine.for_lm`` with the Pallas attention lane, driven by a
          tick-fired ``AdaptationProgram(DiveBatchPolicy)`` that resizes the
          batch at least once, so at least two ``num_micro`` buckets
          compile.  Depth is cut to 2 layers; sequence 2048.
  serve   ``ServeEngine`` over the whole 32-layer model: paged KV cache,
          prompts prefilled in chunks, a late request that shares a prompt
          prefix with an earlier one.  Its tokens, and the logits of the
          paged prefill-then-decode path, are checked against a plain
          full-sequence forward pass.

A failed check raises, so the process exits non-zero.  The lines printed
before the last are bring-up facts (the configuration and its cut,
compiles, compile seconds, steady step times, the batch schedule, logit
errors, peak device bytes), not benchmark numbers.  The last line is one
JSON object naming the device.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.adapt import AdaptationProgram, Clock, DiveBatchPolicy, read_signals  # noqa: E402
from repro.configs import yi_6b  # noqa: E402
from repro.data import TokenStream  # noqa: E402
from repro.dist.plan import ShardingPlan, make_mesh, use_plan  # noqa: E402
from repro.elastic import MeshLadder, reshard  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.optim import sgd  # noqa: E402
from repro.serve import Request, ServeEngine, padded_prompt_len  # noqa: E402
from repro.train import StepEngine, init_state  # noqa: E402
from repro.utils.xla_env import enable_compile_cache  # noqa: E402

SEED = 0

# The readout starts at half the default 1/sqrt(d_model) scale.  The final
# norm leaves unit-RMS activations, so the logits of a random model have
# variance READOUT_SCALE**2 and its expected loss is ln V + READOUT_SCALE**2/2:
# ln V + 1/8 here, where the default scale would put it at ln V + 1/2, on
# the edge of the first-loss check.
READOUT_SCALE = 0.5

# The first loss must lie within this band of ln V: the loss of a model that
# predicts close to uniformly, which a random one does.
FIRST_LOSS_BAND = 0.5

# Pallas-lane bf16 loss against the float32 dense-attention reference on the
# same batch.  bf16 keeps 8 significant bits (relative rounding 2**-9, about
# 0.2%); 0.05 on a loss near 11 is 0.45%, room for that rounding through two
# layers and the 64000-way log-sum-exp, and well under the tenths that a
# wrong scale, mask or head mapping costs.
LOSS_TOL = 0.05

# Serving logits (unit: logits, whose std is READOUT_SCALE = 0.5 here) from
# the paged path against a full-sequence forward pass, both in bf16.  The two
# round at different places through the layers: about 0.2% per rounding,
# compounding over 32 layers to a few percent of the logit scale, i.e. a few
# hundredths, whose maximum over 64000 entries and 32 positions can reach
# about 0.1.  0.25 leaves room for that and still catches a wrong position,
# a stale cache block or a wrong block table, which move logits by whole units.
LOGIT_TOL = 0.25

# The one-chip run (``python chip_smoke.py``).
TRAIN = dict(num_layers=2, seq_len=2048, micro_batch=1, m_max=2, steps=6,
             tick_every=2, lr=0.02)
SERVE = dict(
    num_layers=32,
    # seven prompts of 128..1024 tokens, then an eighth that arrives once the
    # first seven are resident and repeats the first 384 tokens of the 512-token one
    prompt_lens=(128, 160, 256, 384, 512, 768, 1024),
    follower=(4, 384),
    max_new=32, block=128, chunk=512, max_seq=2048,
    # 8 slots x 2048 positions = 16384 tokens of KV at 64 KiB each: 1 GiB
    pool_blocks=128,
    checked=(0, 7),  # requests whose prefill-then-decode logits are compared
)
# ``--four-chips``: 2 layers for both paths; training at dp=fsdp=4, one
# sequence per chip per microbatch, at a length that lets the same batch run
# on one chip for the comparison.
FOUR_TRAIN = dict(num_layers=2, seq_len=512, micro_batch=4, m_max=8, steps=6,
                  tick_every=2, lr=0.02)
FOUR_SERVE = dict(SERVE, num_layers=2)


class CheckFailed(AssertionError):
    """A phase's output is wrong."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise CheckFailed(msg)


def gb(n: float) -> str:
    return f"{n / 1e9:.3f} GB"


def peak_bytes(devices=None) -> str:
    """Peak device bytes in use since the process started, per device."""
    out = []
    for d in devices or jax.devices()[:1]:
        stats = d.memory_stats() or {}
        out.append(gb(stats["peak_bytes_in_use"]) if "peak_bytes_in_use" in stats
                   else "not reported by this backend")
    return ", ".join(out)


def n_params(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def describe(cfg, cut: str) -> str:
    return (f"{cfg.name} widths d_model={cfg.d_model} heads={cfg.num_heads} "
            f"kv_heads={cfg.num_kv_heads} head_dim={cfg.resolved_head_dim} "
            f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} {cfg.param_dtype}; "
            f"cut: {cut}")


def init_weights(cfg, seed: int):
    """Random parameters built inside one jit: the eager init would hold
    float32 temporaries of the whole stacked model on the device."""

    def init(key):
        params = tf.init_params(cfg, key)
        head = params["lm_head"]["kernel"]
        params["lm_head"]["kernel"] = (head * READOUT_SCALE).astype(head.dtype)
        return params

    return jax.jit(init)(jax.random.key(seed))


def reference_loss(cfg, params, batch) -> float:
    """The plain float32 forward: dense attention, float32 weights and
    activations, full-precision matmuls."""
    ref = cfg.replace(param_dtype="float32", compute_dtype="float32",
                      attn_impl="dense")

    def loss(p, b):
        with jax.default_matmul_precision("highest"):
            p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p)
            return tf.loss_fn(ref, p32, b)[0]

    return float(jax.jit(loss)(params, batch))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train_phase(cfg, *, seq_len: int, micro_batch: int, m_max: int, steps: int,
                tick_every: int, lr: float, plan: ShardingPlan | None = None,
                reference: bool = True, log=print) -> dict:
    """Adaptive LM training: ``StepEngine.for_lm`` under a tick-fired
    DiveBatch program.  Under ``plan`` the state is resharded onto it and
    each global batch is split over its dp axes.  Returns the step records,
    the batch schedule and the engine stats."""
    cfg = cfg.replace(attn_impl="pallas")
    dp = plan.dp_size if plan is not None else 1
    params = init_weights(cfg, SEED)
    log(f"train: {n_params(params)} parameters")
    stream = TokenStream(cfg.vocab_size, seed=SEED)

    def put(batch_np):
        if plan is None:
            return {k: jnp.asarray(v) for k, v in batch_np.items()}
        sharding = NamedSharding(plan.mesh, P(tuple(plan.dp)))
        return {k: jax.device_put(v, sharding) for k, v in batch_np.items()}

    if reference:
        first = put(stream.batch(0, micro_batch, seq_len))
        lane = float(jax.jit(lambda p, b: tf.loss_fn(cfg, p, b)[0])(params, first))
        ref = reference_loss(cfg, params, first)
        log(f"train: first-batch loss, Pallas lane bf16 {lane!r} vs float32 "
            f"dense reference {ref!r}: |diff| {abs(lane - ref)!r} (tolerance {LOSS_TOL})")
        check(abs(lane - ref) <= LOSS_TOL,
              f"Pallas-lane loss {lane} is {abs(lane - ref)} from the float32 "
              f"reference {ref} (tolerance {LOSS_TOL})")

    opt = sgd(momentum=0.9)
    state = init_state(params, opt)
    del params
    if plan is not None:
        state = reshard(state, None, plan)
    # delta * n * Delta_hat >= delta, since Delta_hat >= 1/n (Cauchy-Schwarz):
    # with delta = 2 * m_max the first tick resizes the batch to m_max
    policy = DiveBatchPolicy(micro_batch, m_max, delta=2.0 * m_max,
                             dataset_size=None, granule=micro_batch,
                             on_tick=True)
    program = AdaptationProgram(policy, base_lr=lr, estimator="moment",
                                tick_every=tick_every)
    ctx = use_plan(plan) if plan is not None else contextlib.nullcontext()
    records, ticks = [], []
    with ctx:
        engine = StepEngine.for_lm(cfg, opt, micro_batch=micro_batch,
                                   dp_size=dp, attn_impl="pallas")
        m = program.batch_size
        for step in range(steps):
            batch = put(stream.batch(step, m, seq_len))
            compiles = engine.stats.compiles
            t0 = time.perf_counter()
            state, metrics = engine.step(state, batch, program.lr)
            jax.block_until_ready(state)
            dt = time.perf_counter() - t0
            loss = float(metrics["loss"])
            rec = dict(step=step + 1, batch=m, num_micro=m // micro_batch,
                       loss=loss, seconds=dt,
                       compiled=engine.stats.compiles > compiles)
            records.append(rec)
            log(f"train: step {step + 1} batch={m} num_micro={m // micro_batch} "
                f"loss={loss!r} {dt:.4f}s{' (compiled)' if rec['compiled'] else ''}")
            if (step + 1) % tick_every == 0:
                sig, state = read_signals(state, "moment", reset=True,
                                          batch_size=m, loss=loss)
                program.observe(sig, Clock(epoch=step // tick_every,
                                           step=step + 1, boundary="tick"))
                ticks.append(dict(step=step + 1, diversity=sig.diversity,
                                  gns=sig.gns, batch=program.batch_size))
                log(f"train: tick at step {step + 1}: diversity={sig.diversity!r} "
                    f"gns={sig.gns!r} -> batch {m} -> {program.batch_size}")
                m = program.batch_size

    stats = engine.stats
    losses = [r["loss"] for r in records]
    schedule = [r["batch"] for r in records]
    steady = {}
    for r in records:
        if not r["compiled"]:
            steady.setdefault(r["num_micro"], []).append(r["seconds"])
    log(f"train: batch schedule {schedule}; compiles {stats.compiles} "
        f"(num_micro buckets {stats.buckets}) in {stats.compile_s:.2f}s")
    log("train: steady step " + ", ".join(
        f"num_micro={k} median {float(np.median(v)):.4f}s over {len(v)}"
        for k, v in sorted(steady.items())))
    devices = list(plan.mesh.devices.flat) if plan is not None else None
    log(f"train: peak_bytes_in_use {peak_bytes(devices)}")

    check(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    ln_v = math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) < FIRST_LOSS_BAND,
          f"first loss {losses[0]} is not within {FIRST_LOSS_BAND} of ln V = {ln_v}")
    for t in ticks:
        check(math.isfinite(t["diversity"]) and t["diversity"] > 0
              and math.isfinite(t["gns"]) and t["gns"] > 0,
              f"diversity/GNS signals not finite and positive: {t}")
    check(len(set(schedule)) >= 2, f"the batch never resized: {schedule}")
    check(len(set(stats.buckets)) >= 2,
          f"fewer than two num_micro buckets compiled: {stats.buckets}")
    return dict(losses=losses, schedule=schedule, ticks=ticks,
                buckets=list(stats.buckets), compile_s=stats.compile_s,
                steady=steady)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def build_requests(vocab: int, prompt_lens, follower, max_new: int):
    """Random prompts of the given lengths, plus a follower that repeats the
    first ``follower[1]`` tokens of request ``follower[0]`` and has its
    length."""
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, vocab, n, dtype=np.int32) for n in prompt_lens]
    src, shared = follower
    tail = rng.integers(1, vocab, len(prompts[src]) - shared, dtype=np.int32)
    prompts.append(np.concatenate([prompts[src][:shared], tail]))
    return [Request(prompt=p, max_new_tokens=max_new) for p in prompts]


def padded(prompt, granule: int) -> np.ndarray:
    """The prompt as the engine holds it: left-padded to its bucket."""
    out = np.zeros(padded_prompt_len(len(prompt), granule), np.int32)
    out[len(out) - len(prompt):] = prompt
    return out


def incremental_logits(cfg, params, seq, plen: int, *, block: int, chunk: int):
    """Logits at positions ``plen - 1 ..`` of ``seq`` from the paged serving
    path at batch 1: the prompt prefilled in chunks into a block pool, then
    one decode step per remaining token (teacher-forced) — the two model
    programs ServeEngine compiles, with the same pool layout."""
    n_blocks = -(-len(seq) // block)
    pages = tf.init_pages(cfg, n_blocks + 1, block)  # block 0 is the sentinel
    table = np.arange(1, n_blocks + 1, dtype=np.int32)
    row = tf.init_cache(cfg, 1, len(seq), skip=tf.paged_positions(cfg))
    row["len"] = jnp.zeros((1,), jnp.int32)
    prefill = jax.jit(functools.partial(tf.prefill_chunk, cfg))
    decode = jax.jit(lambda p, c, t, pg, tb: tf.decode_step(cfg, p, c, t, pages=pg,
                                                            tables=tb))
    for off in range(0, plen, chunk):
        c = min(chunk, plen - off)
        logits, row, pages = prefill(
            params, row, pages, {"tokens": jnp.asarray(seq[None, off:off + c])},
            np.int32(off), table[:off // block], table[off // block:(off + c) // block],
        )
    out = [logits[0, -1]]
    cache = row
    for tok in seq[plen:]:
        logits, cache, pages = decode(params, cache, np.asarray([[tok]], np.int32),
                                      pages, table[None])
        out.append(logits[0, -1])
    return np.asarray(jnp.stack(out))


def serve_phase(cfg, *, num_layers: int, prompt_lens, follower, max_new: int,
                block: int, chunk: int, max_seq: int, pool_blocks: int, checked,
                elastic: MeshLadder | None = None, log=print) -> dict:
    """Answer the requests through ServeEngine, then check its tokens and
    the paged path's logits against a full-sequence forward pass."""
    cfg = cfg.replace(num_layers=num_layers)
    params = init_weights(cfg, SEED)
    log(f"serve: {n_params(params)} parameters")
    reqs = build_requests(cfg.vocab_size, prompt_lens, follower, max_new)
    eng = ServeEngine(cfg, params, max_slots=len(reqs), max_seq=max_seq,
                      prompt_granule=block, block_size=block,
                      pool_blocks=pool_blocks, prefill_chunk=chunk,
                      attn_impl="pallas", elastic=elastic)
    st = eng.stats

    def counters():
        return (st.compiles, st.prefill_compiles, st.aux_compiles,
                st.prefill_chunks)

    rids = [eng.submit(r) for r in reqs[:-1]]
    steady: dict[int, list[float]] = {}
    t_start = time.perf_counter()
    while eng.busy or len(rids) < len(reqs):
        if len(rids) < len(reqs) and st.prefills >= len(rids):
            rids.append(eng.submit(reqs[-1]))  # the leader's blocks are registered
        before, bucket = counters(), eng.sched.capacity
        t0 = time.perf_counter()
        eng.step()
        dt = time.perf_counter() - t0
        if counters() == before and eng.sched.capacity == bucket and bucket:
            steady.setdefault(bucket, []).append(dt)
    wall = time.perf_counter() - t_start
    eng.pool.check()
    results = [eng.result(r) for r in rids]
    log(f"serve: answered {len(results)} requests in {wall:.3f}s: prompts "
        f"{[len(r.prompt) for r in reqs]} tokens, {max_new} new each; decode "
        f"compiles {st.compiles} (slot buckets {st.buckets}), prefill compiles "
        f"{st.prefill_compiles}, helper compiles {st.aux_compiles}, compile "
        f"{st.compile_s:.2f}s; prefill chunks {st.prefill_chunks}, shared "
        f"prefix blocks {st.shared_blocks}, reshards {st.reshards}, peak pool "
        f"blocks {st.peak_blocks}/{st.pool_blocks}")
    log("serve: steady decode step (host + device) " + ", ".join(
        f"bucket={k} median {float(np.median(v)):.4f}s over {len(v)}"
        for k, v in sorted(steady.items())))
    for r in results:
        check(len(r.tokens) == max_new, f"a request got {len(r.tokens)} of {max_new} tokens")
        check(bool(np.all((r.tokens >= 0) & (r.tokens < cfg.vocab_size))),
              "a token is outside the vocabulary")
    check(st.shared_blocks > 0, "the follower adopted no shared prefix blocks")
    if elastic is not None:
        check(st.reshards >= 1, "the elastic run never resharded")
    del eng  # frees its block pool for the checks below

    # the full-sequence reference: dense attention, one compile at the longest
    # padded prompt + new tokens (logits at a position see only what precedes it)
    dense_cfg = cfg.replace(attn_impl="dense")
    fwd = jax.jit(lambda p, t: tf.forward(dense_cfg, p, {"tokens": t}))
    seqs = [np.concatenate([padded(r.prompt, block), res.tokens[:-1]])
            for r, res in zip(reqs, results)]
    span = max(len(s) for s in seqs)
    gaps, errs = [], []
    for i, (req, res, seq) in enumerate(zip(reqs, results, seqs)):
        plen = len(seq) - (max_new - 1)
        toks = np.zeros((1, span), np.int32)
        toks[0, :len(seq)] = seq
        ref = np.asarray(fwd(params, toks)[0, plen - 1:plen - 1 + max_new])
        # the engine's greedy token must be the reference's top token, up to
        # the tolerance: a logit-level check on the engine's own output
        gap = float(np.max(ref.max(-1) - ref[np.arange(max_new), res.tokens]))
        gaps.append(gap)
        if i in checked:
            inc = incremental_logits(cfg.replace(attn_impl="pallas"), params, seq,
                                     plen, block=block, chunk=chunk)
            errs.append(float(np.max(np.abs(inc - ref))))
    log(f"serve: engine tokens vs full-forward argmax, largest logit gap "
        f"{max(gaps)!r} (per request {gaps}); tolerance {LOGIT_TOL}")
    log(f"serve: paged prefill-then-decode logits vs full forward, largest "
        f"|error| {max(errs)!r} (requests {list(checked)}: {errs}); "
        f"tolerance {LOGIT_TOL}")
    devices = list(elastic.full.plan.mesh.devices.flat) if elastic is not None else None
    log(f"serve: peak_bytes_in_use {peak_bytes(devices)}")
    check(max(gaps) <= LOGIT_TOL, f"an engine token is {max(gaps)} below the "
          f"reference's top logit (tolerance {LOGIT_TOL})")
    check(max(errs) <= LOGIT_TOL, f"paged-path logits are {max(errs)} from the "
          f"full forward (tolerance {LOGIT_TOL})")
    return dict(tokens=[r.tokens.tolist() for r in results], gaps=gaps, errs=errs,
                wall=wall, steady=steady)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chip_phase(cfg, devices, *, train: dict, serve: dict, log=print) -> dict:
    """Data-parallel + FSDP training and elastic serving on four devices,
    each against the same run on ``devices[0]`` alone."""
    check(len(devices) == 4, f"need 4 devices, got {len(devices)}")
    full, train = cfg, dict(train)
    cfg = cfg.replace(num_layers=train.pop("num_layers"))
    log("four: train " + describe(cfg, f"num_layers 32 -> {cfg.num_layers}, seq "
        f"{train['seq_len']}, micro_batch {train['micro_batch']}"))
    one = train_phase(cfg, reference=False, log=lambda s: log("four/1 " + s), **train)
    mesh = make_mesh((4,), ("data",), devices=devices)
    plan = ShardingPlan(mesh=mesh, dp=("data",), fsdp=("data",), tp=None,
                        ep=("data",))
    four = train_phase(cfg, plan=plan, reference=False,
                       log=lambda s: log("four/4 " + s), **train)
    diffs = [abs(a - b) for a, b in zip(one["losses"], four["losses"])]
    log(f"four: train losses one chip {one['losses']} vs dp=fsdp=4 "
        f"{four['losses']}: largest |diff| {max(diffs)!r} (tolerance {LOSS_TOL})")
    check(one["schedule"] == four["schedule"],
          f"batch schedules differ: {one['schedule']} vs {four['schedule']}")
    check(max(diffs) <= LOSS_TOL, f"per-step losses differ by {max(diffs)} "
          f"between one chip and dp=fsdp=4 (tolerance {LOSS_TOL})")

    log("four: serve " + describe(cfg.replace(num_layers=serve["num_layers"]),
                                  f"num_layers 32 -> {serve['num_layers']}"))
    rung1 = serve_phase(full, log=lambda s: log("four/1 " + s), **serve)
    ladder = MeshLadder(devices, granule=1)
    elastic = serve_phase(full, elastic=ladder,
                          log=lambda s: log("four/elastic " + s), **serve)
    same = sum(a == b for x, y in zip(rung1["tokens"], elastic["tokens"])
               for a, b in zip(x, y))
    total = sum(len(x) for x in rung1["tokens"])
    log(f"four: elastic serving (ladder dp {ladder.widths}) tokens equal to the "
        f"one-rung run at {same}/{total} positions; both within {LOGIT_TOL} of "
        f"the full-forward argmax (largest gaps {max(rung1['gaps'])!r}, "
        f"{max(elastic['gaps'])!r})")
    return dict(train_one=one, train_four=four, serve_one=rung1,
                serve_elastic=elastic)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase: dp=fsdp=4 training and "
                         "elastic serving, each against its one-chip run")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"no phase ran", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    log = functools.partial(print, flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
        four_chip_phase(yi_6b.CONFIG, devices[:4], train=FOUR_TRAIN,
                        serve=FOUR_SERVE, log=log)
    else:
        cfg = yi_6b.CONFIG
        train = dict(TRAIN)
        layers = train.pop("num_layers")
        log("train: " + describe(cfg, f"num_layers 32 -> {layers}, seq "
            f"{train['seq_len']}, micro_batch {train['micro_batch']}, attn_impl pallas"))
        train_phase(cfg.replace(num_layers=layers), log=log, **train)
        log("serve: " + describe(cfg, f"none (num_layers {SERVE['num_layers']}), "
            f"attn_impl pallas, block {SERVE['block']}, prefill chunk {SERVE['chunk']}"))
        serve_phase(cfg, log=log, **SERVE)
    log(f"total: {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
