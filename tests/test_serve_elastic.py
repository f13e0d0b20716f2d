"""repro.serve: the rebuilt ServeEngine — golden decode equivalence on a
fixed full mesh, on every ladder rung, and across live rung transitions;
(bucket, rung) compile-cache accounting via ServeStats; the continuous
batching retire/refill fix for the old chunked-generate waste; and the
ring/SSM slot-insertion substrate."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.dist.plan import ShardingPlan, make_mesh, use_plan
from repro.elastic import MeshLadder
from repro.models import transformer as tf
from repro.serve import Request, ServeEngine, padded_prompt_len

MAX_SEQ = 64
GRANULE = 8  # prompt granule: every test prompt pads into the 8-bucket


def _cfg(**kw):
    base = dict(
        name="t", family="dense", num_layers=2, d_model=32, num_heads=4,
        num_kv_heads=2, d_ff=64, vocab_size=61, pattern=("attn",),
        param_dtype="float32", compute_dtype="float32", xent_chunk=8,
        remat=False,
    )
    base.update(kw)
    return ModelConfig(**base)


CFG = _cfg()
PARAMS = tf.init_params(CFG, jax.random.key(0))

# the golden trace: r0 long enough to stay live across every arrival wave,
# prompts all inside the single pow2 prompt bucket (lens <= 8)
_LENS = [5, 3, 8, 2, 6, 4, 7, 5]
_MAX_NEW = [24, 12, 12, 6, 6, 6, 6, 6]


def _requests():
    rng = np.random.default_rng(7)
    return [
        Request(prompt=rng.integers(1, CFG.vocab_size, size=n).astype(np.int32),
                max_new_tokens=m)
        for n, m in zip(_LENS, _MAX_NEW)
    ]


def _oracle(cfg, params, req, max_seq=MAX_SEQ, granule=GRANULE):
    """Fully independent single-request reference: greedy continuation by
    re-prefilling the whole (padded prompt + generated prefix) each step —
    no serve engine, no scheduler, no decode cache."""
    prompt = np.asarray(req.prompt, np.int32)
    plen = padded_prompt_len(len(prompt), granule)
    seq = np.zeros(plen, np.int32)
    seq[plen - len(prompt):] = prompt
    seq = list(seq)
    budget = min(req.max_new_tokens, max_seq - plen + 1)
    pref = jax.jit(lambda p, b: tf.prefill_step(cfg, p, b)[0])
    out = []
    while len(out) < budget:
        logits = pref(params, {"tokens": jnp.asarray(np.asarray(seq)[None])})
        out.append(int(jnp.argmax(logits[0, -1])))
        if req.eos_id is not None and out[-1] == req.eos_id:
            break
        seq.append(out[-1])
    return out


@pytest.fixture(scope="module")
def golden():
    reqs = _requests()
    return reqs, [_oracle(CFG, PARAMS, r) for r in reqs]


def _tokens(results):
    return [r.tokens.tolist() for r in results]


# ---------------------------------------------------------------------------
# golden decode equivalence (the tentpole acceptance tests)
# ---------------------------------------------------------------------------


def test_single_device_matches_oracle_with_cache_accounting(golden):
    reqs, expected = golden
    eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE)
    assert _tokens(eng.generate(reqs)) == expected
    stats = eng.stats
    assert stats.retired == len(reqs)
    assert stats.tokens == sum(len(t) for t in expected)
    assert stats.tokens_per_sec > 0  # the windowed ThroughputWindow rate
    # (bucket, rung) accounting mirrors EngineStats
    assert stats.compiles == len(set(zip(stats.buckets, stats.rungs)))
    assert all(b in (1, 2, 4) for b in stats.buckets)  # pow2 slot lattice
    assert stats.bucket_hits + stats.bucket_misses == stats.steps
    assert stats.bucket_misses == stats.compiles


def test_fixed_full_mesh_matches_oracle(golden):
    reqs, expected = golden
    mesh = make_mesh((8,), ("data",))
    with use_plan(ShardingPlan(mesh=mesh, tp=None)):
        eng = ServeEngine(CFG, PARAMS, max_slots=8, max_seq=MAX_SEQ,
                          prompt_granule=GRANULE)
        assert _tokens(eng.generate(reqs)) == expected
    assert eng.stats.reshards == 0  # pinned mesh: no ladder, no transitions


@pytest.mark.slow
def test_every_rung_matches_oracle(golden):
    """Token-identical outputs on EACH ladder rung individually (the serving
    analogue of PR 3's golden elastic trajectory test)."""
    reqs, expected = golden
    ladder = MeshLadder(granule=1)
    assert ladder.widths == [1, 2, 4, 8]
    for rung in ladder:
        with use_plan(rung.plan):
            eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                              prompt_granule=GRANULE)
            assert _tokens(eng.generate(reqs)) == expected, f"rung dp{rung.dp}"


def test_elastic_live_rung_transitions_golden(golden):
    """A ramping arrival trace drives >= 2 LIVE rung transitions (grow with
    the wave, shrink on the drain) — outputs stay token-identical and the
    compile cache stays within the (bucket, rung) accounting."""
    reqs, expected = golden
    ladder = MeshLadder(granule=1)
    eng = ServeEngine(CFG, PARAMS, max_slots=8, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, elastic=ladder)
    rids = [eng.submit(reqs[0])]
    for _ in range(2):
        eng.step()
    rungs_seen = {eng.rung.index}
    rids += [eng.submit(r) for r in reqs[1:3]]
    for _ in range(2):
        eng.step()
    rungs_seen.add(eng.rung.index)
    rids += [eng.submit(r) for r in reqs[3:]]
    while eng.step():
        rungs_seen.add(eng.rung.index)

    assert _tokens([eng.result(rid) for rid in rids]) == expected
    stats = eng.stats
    assert stats.reshards >= 2  # >= 2 genuine live transitions
    assert len(rungs_seen) >= 2
    assert len(set(stats.rungs)) >= 2
    # (bucket, rung) cache accounting via ServeStats
    assert stats.compiles == len(set(zip(stats.buckets, stats.rungs)))
    assert stats.bucket_hits > 0  # revisited (bucket, rung) on the drain
    for bucket, rung in zip(stats.buckets, stats.rungs):
        assert bucket in (1, 2, 4, 8)
        assert rung == ladder.rung_for_batch(bucket).index


def test_elastic_under_ambient_plan_raises():
    mesh = make_mesh((8,), ("data",))
    with use_plan(ShardingPlan(mesh=mesh, tp=None)):
        with pytest.raises(ValueError, match="ambig"):
            ServeEngine(CFG, PARAMS, elastic=MeshLadder(granule=1))


# ---------------------------------------------------------------------------
# the continuous-batching fix: retire/refill instead of chunk hostage-taking
# ---------------------------------------------------------------------------


def test_mid_batch_retirement_bounds_decode_work():
    """The old ``_generate_batch`` decoded every slot for the chunk-max
    ``max_new`` (one long request held the whole chunk; a finished slot kept
    being decoded).  The Scheduler retires/refills per slot: total decoded
    lanes must track the per-request work, not slots x chunk-max."""
    rng = np.random.default_rng(3)
    long = Request(prompt=rng.integers(1, 61, size=5).astype(np.int32),
                   max_new_tokens=40)
    shorts = [Request(prompt=rng.integers(1, 61, size=4).astype(np.int32),
                      max_new_tokens=4) for _ in range(7)]
    eng = ServeEngine(CFG, PARAMS, max_slots=8, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, shrink_patience=0)
    results = eng.generate([long] + shorts)
    decode_steps = [r.steps - 1 for r in results]  # token 1 is from prefill
    assert results[0].steps == 40
    assert all(r.steps == 4 for r in results[1:])
    # decoded lanes <= per-request decode steps + refill slack
    assert eng.stats.slot_steps <= sum(decode_steps) + eng.sched.max_slots
    # and strictly far below the old chunked cost (8 slots x 39 steps)
    assert eng.stats.slot_steps < (8 * max(decode_steps)) // 2
    assert eng.stats.resizes >= 2  # shrank after the shorts retired


def test_queue_refills_freed_slots():
    rng = np.random.default_rng(4)
    reqs = [Request(prompt=rng.integers(1, 61, size=3).astype(np.int32),
                    max_new_tokens=3) for _ in range(10)]
    eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE)
    results = eng.generate(reqs)
    assert all(r.steps == 3 for r in results)
    assert eng.stats.prefills == 10  # every request admitted exactly once
    assert max(eng.stats.buckets) <= 4  # capacity never exceeded max_slots


def test_eos_retires_slot_early_without_disturbing_neighbours():
    reqs = _requests()[:4]
    eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE)
    base = _tokens(eng.generate(reqs))
    eos = base[0][2]  # retire request 0 exactly at its 3rd token
    reqs2 = _requests()[:4]
    reqs2[0].eos_id = int(eos)
    eng2 = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                       prompt_granule=GRANULE)
    got = _tokens(eng2.generate(reqs2))
    assert got[0] == base[0][:3]  # stopped at EOS, token-identically
    assert got[1:] == base[1:]  # slot retirement never perturbs neighbours


# ---------------------------------------------------------------------------
# slot-insertion substrate: windowed ring buffers and SSM state
# ---------------------------------------------------------------------------


def test_windowed_ring_insertion_matches_full_recompute():
    """A non-pow2 window forces a genuine ring rotation on slot insertion
    (pow2 prompts make ``plen % window == 0`` whenever window is pow2)."""
    cfg = _cfg(pattern=("attn_local",), window=6)
    params = tf.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(5)
    req = Request(prompt=rng.integers(1, 61, size=12).astype(np.int32),
                  max_new_tokens=6)
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=48,
                      prompt_granule=GRANULE)
    got = _tokens(eng.generate([req]))[0]
    assert got == _oracle(cfg, params, req, max_seq=48)


def test_ssm_slot_state_matches_scalar_decode():
    cfg = ModelConfig(name="t", family="ssm", num_layers=2, d_model=32,
                      num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=61,
                      pattern=("mamba",), param_dtype="float32",
                      compute_dtype="float32", xent_chunk=8, ssm_chunk=8,
                      remat=False)
    params = tf.init_params(cfg, jax.random.key(2))
    rng = np.random.default_rng(6)
    prompt = rng.integers(1, 61, size=12).astype(np.int32)
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=48, prompt_granule=8)
    got = _tokens(eng.generate([Request(prompt=prompt, max_new_tokens=6)]))[0]

    # scalar-path reference: feed the padded prompt token by token
    plen = padded_prompt_len(len(prompt), 8)
    padded = np.zeros(plen, np.int32)
    padded[plen - len(prompt):] = prompt
    cache = tf.init_cache(cfg, 1, 48)
    dec = jax.jit(lambda p, c, t: tf.decode_step(cfg, p, c, t))
    logits = None
    for t in padded:
        logits, cache = dec(params, cache, jnp.asarray([[t]], jnp.int32))
    ref = []
    for _ in range(6):
        tok = int(jnp.argmax(logits[0, -1]))
        ref.append(tok)
        logits, cache = dec(params, cache, jnp.asarray([[tok]], jnp.int32))
    assert got == ref


# ---------------------------------------------------------------------------
# sampling + guards
# ---------------------------------------------------------------------------


def test_categorical_sampling_is_per_request_deterministic():
    """Sampled decode derives its key from (engine seed, request id,
    position) — the slot layout / co-batching must not change a request's
    tokens (request ids follow submit order, so identical traces at
    different slot counts compare key-for-key)."""
    rng = np.random.default_rng(8)
    reqs = [Request(prompt=rng.integers(1, 61, size=4).astype(np.int32),
                    max_new_tokens=5) for _ in range(3)]

    def run(slots):
        eng = ServeEngine(CFG, PARAMS, max_slots=slots, max_seq=MAX_SEQ,
                          prompt_granule=GRANULE, sampler="categorical",
                          temperature=0.8, seed=11)
        return _tokens(eng.generate(reqs))

    wide, narrow = run(4), run(1)
    assert wide == narrow
    assert all(0 <= t < CFG.vocab_size for toks in wide for t in toks)


def test_prefill_only_requests_never_decode():
    """max_new_tokens=1 is satisfied by the prefill logits alone: the slot
    retires at admission and the batch never pays a decode step for it."""
    rng = np.random.default_rng(9)
    reqs = [Request(prompt=rng.integers(1, 61, size=4).astype(np.int32),
                    max_new_tokens=1) for _ in range(3)]
    eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE)
    results = eng.generate(reqs)
    assert all(r.steps == 1 for r in results)
    assert eng.stats.steps == 0 and eng.stats.retired == 3
    assert eng.stats.tokens_per_sec > 0  # prefill tokens feed the rate too
    assert _tokens(results) == [_oracle(CFG, PARAMS, r) for r in reqs]


def test_prompt_beyond_max_seq_raises():
    eng = ServeEngine(CFG, PARAMS, max_slots=2, max_seq=16, prompt_granule=8)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(Request(prompt=np.ones(17, np.int32), max_new_tokens=2))


def test_unknown_sampler_raises():
    with pytest.raises(ValueError, match="sampler"):
        ServeEngine(CFG, PARAMS, sampler="beam")


@pytest.mark.slow
def test_every_rung_matches_oracle_pallas(golden):
    """The PR 7 kernel lane under the elastic ladder: every rung, with
    attn_impl='pallas' (fused paged decode + Pallas prefill), stays
    token-identical to the single-device XLA oracle."""
    reqs, expected = golden
    ladder = MeshLadder(granule=1)
    for rung in ladder:
        with use_plan(rung.plan):
            eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                              prompt_granule=GRANULE, attn_impl="pallas")
            assert _tokens(eng.generate(reqs)) == expected, f"rung dp{rung.dp}"


def test_elastic_pallas_traces_kernels_under_the_rung_plan(golden, monkeypatch):
    """On an elastic rung the engine traces under that rung's plan, so every
    Pallas kernel goes through dist.plan.shard_local on the rung's mesh: the
    TPU compiler refuses to partition a Mosaic kernel itself, which interpret
    mode on the CPU would never show."""
    from repro.dist.plan import current_plan

    seen = set()
    real = tf.shard_local

    def spy(fn, args, in_roles, out_roles):
        plan = current_plan()
        seen.add((fn.func.__name__, plan.mesh.size if plan is not None else 1))
        return real(fn, args, in_roles, out_roles)

    monkeypatch.setattr(tf, "shard_local", spy)
    reqs, expected = golden
    eng = ServeEngine(CFG, PARAMS, max_slots=8, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, attn_impl="pallas",
                      elastic=MeshLadder(granule=1))
    assert _tokens(eng.generate(reqs)) == expected
    assert eng.stats.reshards >= 1
    # prefill chunks and decode steps alike, traced on the 8-device rung
    assert {("chunk_attention", 8), ("paged_decode_attention", 8)} <= seen


def test_elastic_pallas_gathers_one_layer_of_the_pool(golden):
    """The decode scan carries the whole pool; on a rung's mesh the kernel's
    pool operand is gathered onto every shard, and what is gathered is one
    layer's slice per layer, never the stack of every layer."""
    reqs, expected = golden
    eng = ServeEngine(CFG, PARAMS, max_slots=8, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, attn_impl="pallas",
                      elastic=MeshLadder(granule=1))
    assert _tokens(eng.generate(reqs)) == expected
    stack = eng._pages["pos0"]["k"].shape
    dims = ",".join(map(str, stack[1:]))
    texts = [exe.as_text() for key, exe in eng._exes.items() if key[0] == "decode"]
    gathered = [re.findall(rf"= f32\[(\d+),{dims}\]\S* all-gather\(", t) for t in texts]
    assert all(n == "1" for g in gathered for n in g), gathered
    assert any(gathered), "no rung gathered the pool: none was mapped over shards"
