"""Paged serving: chunked prefill golden vs the re-prefill oracle, prefix
sharing (the shared-system-prompt case costs ONE prefill), pool-footprint
scaling, pool-gated admission, and the PR 6 bugfix satellites (shrink-streak
reset on drain, cfg.attn_impl honored in prefill, decode budget from the
TRUE prompt length)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hlo import pool_ops
from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.serve import Request, ServeEngine, padded_prompt_len

MAX_SEQ = 64
GRANULE = 8


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


def _oracle(cfg, params, req, max_seq=MAX_SEQ, granule=GRANULE):
    """Greedy re-prefill reference with the satellite-3 budget semantics:
    headroom from the TRUE prompt length (padding costs table entries in the
    paged layout, not decode budget)."""
    prompt = np.asarray(req.prompt, np.int32)
    plen = padded_prompt_len(len(prompt), granule)
    seq = np.zeros(plen, np.int32)
    seq[plen - len(prompt):] = prompt
    seq = list(seq)
    budget = min(req.max_new_tokens, max_seq - len(prompt) + 1)
    pref = jax.jit(lambda p, b: tf.prefill_step(cfg, p, b)[0])
    out = []
    while len(out) < budget:
        logits = pref(params, {"tokens": jnp.asarray(np.asarray(seq)[None])})
        out.append(int(jnp.argmax(logits[0, -1])))
        if req.eos_id is not None and out[-1] == req.eos_id:
            break
        seq.append(out[-1])
    return out


def _decode_oracle(cfg, params, req, max_seq=MAX_SEQ, granule=GRANULE):
    """Token-by-token decode_step reference (mamba's chunked prefill scan
    needs chunk-multiple lengths, so hybrid configs are checked against the
    scalar recurrence instead of re-prefill)."""
    prompt = np.asarray(req.prompt, np.int32)
    plen = padded_prompt_len(len(prompt), granule)
    padded = np.zeros(plen, np.int32)
    padded[plen - len(prompt):] = prompt
    budget = min(req.max_new_tokens, max_seq - len(prompt) + 1)
    cache = tf.init_cache(cfg, 1, max_seq)
    dec = jax.jit(lambda p, c, t: tf.decode_step(cfg, p, c, t))
    logits = None
    for t in padded:
        logits, cache = dec(params, cache, jnp.asarray([[int(t)]], jnp.int32))
    out = [int(jnp.argmax(logits[0, -1]))]
    while len(out) < budget:
        logits, cache = dec(params, cache,
                            jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


def _tokens(results):
    return [r.tokens.tolist() for r in results]


def _reqs(lens, max_new, seed=7, shared_prefix=0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, CFG.vocab_size, size=shared_prefix).astype(np.int32)
    out = []
    for n, m in zip(lens, max_new):
        tail = rng.integers(1, CFG.vocab_size, size=n - shared_prefix)
        out.append(Request(
            prompt=np.concatenate([prefix, tail.astype(np.int32)]),
            max_new_tokens=m,
        ))
    return out


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_oracle():
    reqs = _reqs([20, 27, 12], [8, 6, 8])
    eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, prefill_chunk=8)
    assert _tokens(eng.generate(reqs)) == [_oracle(CFG, PARAMS, r) for r in reqs]
    # plens 32, 32, 16 at chunk 8 -> 4 + 4 + 2 chunk programs executed
    assert eng.stats.prefill_chunks == 10
    assert eng.stats.prefills == 3
    # paging adds ZERO decode compile keys (pool shape is engine-lifetime)
    st = eng.stats
    assert st.compiles == len(set(zip(st.buckets, st.rungs)))


def test_chunk_boundaries_interleave_with_decode():
    """A long prompt loads one chunk per boundary; the already-running
    request keeps decoding every one of those boundaries."""
    short, long = _reqs([4, 30], [12, 4], seed=3)
    eng = ServeEngine(CFG, PARAMS, max_slots=2, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, prefill_chunk=8)
    r0 = eng.submit(short)
    eng.step()  # prefill short (token 1) + decode (token 2)
    r1 = eng.submit(long)  # plen 32 -> 4 chunks -> 4 boundaries to load
    for k in range(3):
        grew = len(eng.sched._tokens[r0])
        eng.step()
        assert len(eng._jobs) == 1  # still loading...
        assert len(eng.sched._tokens[r0]) == grew + 1  # ...but decode ran
        assert len(eng.sched._tokens[r1]) == 0
    eng.step()  # final chunk: token 1 (prefill) + token 2 (same-boundary decode)
    assert len(eng._jobs) == 0 and len(eng.sched._tokens[r1]) == 2
    eng.drain()
    assert [eng.result(r).tokens.tolist() for r in (r0, r1)] == \
        [_oracle(CFG, PARAMS, r) for r in (short, long)]
    assert eng.stats.prefill_chunks == 1 + 4


# ---------------------------------------------------------------------------
# prefix sharing
# ---------------------------------------------------------------------------


def test_shared_full_prompt_costs_one_prefill():
    """N requests with the same prompt: one prefill total — later arrivals
    replay the cached end-of-prompt state (instant admission)."""
    first = _reqs([12], [6], seed=5)[0]
    eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE)
    base = _tokens(eng.generate([first]))[0]
    assert eng.stats.prefill_chunks == 1
    again = [Request(prompt=first.prompt.copy(), max_new_tokens=m)
             for m in (4, 6, 2)]
    got = _tokens(eng.generate(again))
    assert eng.stats.prefill_chunks == 1  # STILL one: zero recompute
    assert eng.stats.shared_prefill_hits == 3
    assert eng.stats.prefills == 4
    assert got == [base[:4], base, base[:2]]  # greedy: same stream, truncated
    assert _tokens(eng.generate([again[0]]))[0] == base[:4]  # survives drains


def test_shared_prefix_prefills_only_the_tail():
    """Same-length prompts sharing a raw prefix share the (pad + prefix)
    blocks; only the divergent tail chunk is computed for the second."""
    a, b = _reqs([24, 24], [5, 5], seed=9, shared_prefix=16)
    assert a.prompt[:16].tolist() == b.prompt[:16].tolist()
    assert a.prompt[16:].tolist() != b.prompt[16:].tolist()
    eng = ServeEngine(CFG, PARAMS, max_slots=2, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, prefill_chunk=8)
    got_a = _tokens(eng.generate([a]))[0]
    assert eng.stats.prefill_chunks == 4  # plen 32
    got_b = _tokens(eng.generate([b]))[0]
    # 8 pad + 16 shared = 3 adopted blocks; only the last chunk runs
    assert eng.stats.prefill_chunks == 5
    assert eng.stats.shared_blocks == 3
    assert got_a == _oracle(CFG, PARAMS, a)
    assert got_b == _oracle(CFG, PARAMS, b)


def test_prefix_sharing_disabled_recomputes():
    first = _reqs([12], [6], seed=5)[0]
    eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, prefix_sharing=False)
    base = _tokens(eng.generate([first]))[0]
    rep = Request(prompt=first.prompt.copy(), max_new_tokens=6)
    assert _tokens(eng.generate([rep]))[0] == base
    assert eng.stats.prefill_chunks == 2  # no sharing: both computed
    assert eng.stats.shared_prefill_hits == 0


def test_hybrid_shared_prompt_replays_ring_and_ssm_state():
    """Non-paged state (windowed ring, SSM) lives in the cached row snapshot
    — a full-prompt hit must replay it bit-exactly."""
    cfg = _cfg(pattern=("attn", "attn_local", "mamba"), num_layers=3,
               window=6, ssm_chunk=8)
    params = tf.init_params(cfg, jax.random.key(4))
    req = _reqs([20], [6], seed=11)[0]
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE)
    base = _tokens(eng.generate([req]))[0]
    assert base == _decode_oracle(cfg, params, req)
    rep = Request(prompt=req.prompt.copy(), max_new_tokens=6)
    assert _tokens(eng.generate([rep]))[0] == base
    assert eng.stats.shared_prefill_hits == 1
    assert eng.stats.prefill_chunks == 1


def test_hybrid_chunked_prefill_matches_whole_prompt():
    """Chunked prefill threads ring rotations and SSM (h, conv) state across
    chunk boundaries: 8-token chunks == whole-prompt prefill == oracle."""
    cfg = _cfg(pattern=("attn", "attn_local", "mamba"), num_layers=3,
               window=6, ssm_chunk=8)
    params = tf.init_params(cfg, jax.random.key(4))
    reqs = _reqs([20, 13], [6, 8], seed=12)
    expected = [_decode_oracle(cfg, params, r) for r in reqs]
    for chunk in (0, 8):
        eng = ServeEngine(cfg, params, max_slots=2, max_seq=MAX_SEQ,
                          prompt_granule=GRANULE, prefill_chunk=chunk)
        assert _tokens(eng.generate(reqs)) == expected, f"chunk={chunk}"


# ---------------------------------------------------------------------------
# pool footprint
# ---------------------------------------------------------------------------


def test_peak_blocks_tracks_resident_tokens():
    """The acceptance bound: peak pool usage scales with tokens actually
    resident, far below the dense max_slots * max_seq preallocation."""
    reqs = _reqs([8, 8, 8, 8], [8, 8, 8, 8], seed=13)
    eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, prefix_sharing=False)
    eng.generate(reqs)
    st = eng.stats
    # 4 concurrent requests x (1 prompt block + 1 decode block)
    assert 4 <= st.peak_blocks <= 8
    assert st.peak_blocks * st.block_size <= (4 * MAX_SEQ) // 4
    assert st.pool_blocks > st.peak_blocks
    eng.pool.check()
    assert eng.pool.live == 0  # zero leaked blocks after drain


def test_small_pool_gates_admission_without_exhaustion():
    """A pool too small for two concurrent requests serializes them through
    the admission gate — never an exhausted pool mid-decode."""
    reqs = _reqs([8, 8], [8, 8], seed=14)
    eng = ServeEngine(CFG, PARAMS, max_slots=2, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, pool_blocks=4,
                      prefix_sharing=False)  # 3 usable; each request needs 2
    assert _tokens(eng.generate(reqs)) == [_oracle(CFG, PARAMS, r) for r in reqs]
    assert eng.stats.peak_blocks <= 3
    eng.pool.check()


def test_single_request_larger_than_pool_raises():
    eng = ServeEngine(CFG, PARAMS, max_slots=2, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, pool_blocks=2)
    with pytest.raises(ValueError, match="pool"):
        eng.submit(_reqs([20], [8])[0])


# ---------------------------------------------------------------------------
# satellite 3: decode budget from the TRUE prompt length
# ---------------------------------------------------------------------------


def test_budget_from_true_prompt_length_near_max_seq():
    """A 60-token prompt pads to plen 64 == max_seq; the padded-length budget
    ``max_seq - plen + 1`` used to truncate it to ONE token.  The paged
    layout charges padding to table entries, so the request keeps
    ``max_seq - 60 + 1 = 5``."""
    req = _reqs([60], [5], seed=15)[0]
    eng = ServeEngine(CFG, PARAMS, max_slots=2, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE)
    got = _tokens(eng.generate([req]))[0]
    assert len(got) == 5
    assert got == _oracle(CFG, PARAMS, req)


def test_budget_boundary_full_length_prompt():
    req = _reqs([64], [9], seed=16)[0]  # no padding: budget == 1
    eng = ServeEngine(CFG, PARAMS, max_slots=2, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE)
    got = _tokens(eng.generate([req]))[0]
    assert len(got) == 1
    assert got == _oracle(CFG, PARAMS, req)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(_reqs([65], [2], seed=16)[0])


# ---------------------------------------------------------------------------
# satellite 1: shrink streak resets when the engine drains
# ---------------------------------------------------------------------------


def test_shrink_streak_resets_on_drain():
    """Trace A drains mid-streak (a dip was being ridden out when the last
    request retired).  Trace B's first boundaries dip again: the patience
    budget must start FRESH, not inherit trace A's streak and shrink early."""
    eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, shrink_patience=2)
    eng.generate(_reqs([4, 4], [2, 4], seed=17))  # retire at different steps
    assert eng.sched.capacity == 2  # bucket persists across the drain

    eng.submit(_reqs([4], [8], seed=18)[0])  # target 1 < bucket 2: a dip
    for boundary in range(2):
        eng.step()
        assert eng.sched.capacity == 2, f"shrank early at boundary {boundary}"
    eng.step()  # patience exhausted on the THIRD consecutive dip
    assert eng.sched.capacity == 1
    eng.drain()


# ---------------------------------------------------------------------------
# satellite 2: prefill honors cfg.attn_impl
# ---------------------------------------------------------------------------


def test_prefill_honors_attn_impl(monkeypatch):
    """prefill_step used to hardcode the auto heuristic; a pinned
    ``attn_impl='flash'``/'pallas' must actually take that path (and agree
    with dense numerically)."""
    flash_calls, pallas_calls = [], []
    orig_flash = tf.attn_lib.flash_attention
    orig_pallas = tf.kernels_attn.flash_attention

    monkeypatch.setattr(tf.attn_lib, "flash_attention",
                        lambda *a, **kw: (flash_calls.append(1), orig_flash(*a, **kw))[1])
    monkeypatch.setattr(tf.kernels_attn, "flash_attention",
                        lambda *a, **kw: (pallas_calls.append(1), orig_pallas(*a, **kw))[1])
    rng = np.random.default_rng(19)
    batch = {"tokens": jnp.asarray(
        rng.integers(1, CFG.vocab_size, size=(1, 128)).astype(np.int32))}
    out = {}
    for impl in ("dense", "flash", "auto", "pallas"):
        cfg = _cfg(attn_impl=impl, flash_q_block=64, flash_kv_block=64)
        before_f, before_p = len(flash_calls), len(pallas_calls)
        logits, _ = tf.prefill_step(cfg, PARAMS, batch)
        out[impl] = np.asarray(logits)
        # auto picks dense at s=128 (<= FLASH_THRESHOLD); pinned impls obeyed
        assert (len(flash_calls) > before_f) == (impl == "flash"), impl
        assert (len(pallas_calls) > before_p) == (impl == "pallas"), impl
    np.testing.assert_allclose(out["flash"], out["dense"], atol=2e-4, rtol=2e-5)
    np.testing.assert_allclose(out["pallas"], out["dense"], atol=2e-4, rtol=2e-5)
    np.testing.assert_array_equal(out["auto"], out["dense"])


def test_auto_threshold_unified_on_config_constant():
    """Satellite: the auto fork reads ONE constant — choose_attention and
    resolve_impl flip at the same configured threshold."""
    from repro.configs.base import FLASH_THRESHOLD
    from repro.models import attention as attn_lib

    assert attn_lib.choose_attention(FLASH_THRESHOLD, FLASH_THRESHOLD) \
        is not attn_lib.flash_attention  # at threshold: dense
    assert attn_lib.choose_attention(FLASH_THRESHOLD + 1, 1) \
        is attn_lib.flash_attention     # past it: flash
    cfg = _cfg(flash_q_block=8)
    assert attn_lib.resolve_impl(cfg, FLASH_THRESHOLD) == "dense"
    assert attn_lib.resolve_impl(cfg, FLASH_THRESHOLD + 8) == "flash"
    lowered = _cfg(flash_threshold=64, flash_q_block=8)
    assert attn_lib.resolve_impl(lowered, 72) == "flash"
    assert attn_lib.resolve_impl(lowered.replace(attn_impl="pallas"), 8) == "pallas"


# ---------------------------------------------------------------------------
# PR 7: the Pallas kernel lane on the serving hot loop
# ---------------------------------------------------------------------------


def test_pallas_engine_token_identity():
    """attn_impl='pallas' — fused paged-decode attention + Pallas chunked
    prefill — must be TOKEN-IDENTICAL to the re-prefill oracle and to the
    XLA engine on the same workload (the lane is a drop-in, not an
    approximation)."""
    reqs = _reqs([20, 27, 12, 5], [8, 6, 8, 10], seed=23)
    expected = [_oracle(CFG, PARAMS, r) for r in reqs]
    eng = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, prefill_chunk=8,
                      attn_impl="pallas")
    assert eng.cfg.attn_impl == "pallas"
    assert _tokens(eng.generate(reqs)) == expected
    xla = ServeEngine(CFG, PARAMS, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, prefill_chunk=8)
    assert _tokens(xla.generate(reqs)) == expected


def test_pallas_engine_softcap_prefix_sharing():
    """The fused lane under attention softcap AND copy-on-write prefix
    sharing: same tokens as the XLA engine, and sharing still skips real
    prefill work."""
    cfg = _cfg(attn_softcap=30.0)
    params = tf.init_params(cfg, jax.random.key(1))
    reqs = _reqs([18, 18, 22], [6, 6, 5], seed=5, shared_prefix=16)
    xla = ServeEngine(cfg, params, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, prefill_chunk=8)
    want = _tokens(xla.generate(reqs))
    eng = ServeEngine(cfg, params, max_slots=4, max_seq=MAX_SEQ,
                      prompt_granule=GRANULE, prefill_chunk=8,
                      attn_impl="pallas")
    assert _tokens(eng.generate(reqs)) == want
    # sharing accounting is lane-independent: the kernel lane skipped the
    # same prefill work the XLA lane did
    assert eng.stats.shared_prefill_hits == xla.stats.shared_prefill_hits
    assert eng.stats.prefill_chunks == xla.stats.prefill_chunks


# ---------------------------------------------------------------------------
# the pool stays one buffer across the layer scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pattern", [("attn",), ("attn", "attn_local", "mamba")],
                         ids=["dense", "hybrid"])
@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_pool_is_updated_in_place_across_the_layer_scan(program, pattern):
    """The donated paged programs carry the pool through the layer scan and
    scatter into it: the optimized module holds no pool-shaped copy,
    broadcast or dynamic-update-slice (a per-layer slice restacked as scan
    output, its zero-filled stack, the copy back), only the scatter."""
    cfg = _cfg(pattern=pattern, num_layers=2 * len(pattern), window=6,
               ssm_chunk=8)
    params = tf.init_params(cfg, jax.random.key(0))
    nb, blk, b, n_max = 37, 8, 4, 5
    pages = tf.init_pages(cfg, nb, blk)
    skip = tf.paged_positions(cfg)
    if program == "decode_step":
        cache = dict(tf.init_cache(cfg, b, n_max * blk, skip=skip),
                     len=jnp.zeros((b,), jnp.int32))

        def fn(params, cache, pages, tables, toks):
            return tf.decode_step(cfg, params, cache, toks, pages=pages,
                                  tables=tables)

        args = (params, cache, pages, jnp.zeros((b, n_max), jnp.int32),
                jnp.zeros((b, 1), jnp.int32))
        donate = (1, 2)
    else:
        row = dict(tf.init_cache(cfg, 1, n_max * blk, skip=skip),
                   len=jnp.zeros((1,), jnp.int32))

        def fn(params, pages, row, toks, ptab, wtab, off):
            return tf.prefill_chunk(cfg, params, row, pages,
                                    {"tokens": toks}, off, ptab, wtab)

        args = (params, pages, row, jnp.zeros((1, 2 * blk), jnp.int32),
                jnp.zeros((2,), jnp.int32), jnp.asarray([3, 4], jnp.int32),
                jnp.int32(2 * blk))
        donate = (1, 2)
    text = jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()
    shape = "f32[" + ",".join(map(str, pages["pos0"]["k"].shape)) + "]"
    ops = pool_ops(text, shape)
    kinds = {op for _, op in ops}
    assert kinds <= {"parameter", "get-tuple-element", "scatter"}, ops
    # K and V of every paged position, written in place
    assert sum(op == "scatter" for _, op in ops) >= 2 * len(pages), ops
