"""Paged serving under a closed loop: ``ServeEngine.submit`` and
``ServeEngine.step`` with greedy decoding, driven by ``clients`` callers that
each send their next request the moment the last one completes.

Set-up makes the weights on the device from the seed in one jitted call,
builds the engine, and warms every program the mix can reach: each padded
prompt length with every number of shared leading blocks (and a full
repeat), and each decode slot bucket growing and shrinking.  The loop then
starts and runs ``ramp_s`` before the window opens.

Per request the driver keeps the send time and the end of each step that
delivered tokens to it: TTFT is send to the first token, ITL every gap
between consecutive tokens.  After the window (and the traced stretch of a
``--trace 1`` run) the engine is freed and a sample of the requests finished
in the window, drawn from the seed with the longest among them, is run
through the plain reference the configuration names
(``harness.load_reference``), which also makes the weights: the compared
number is the widest gap by which a served token's logit lies below the
reference's best at that position.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.traffic import requests as gen


class Loop:
    """The closed loop: clients, send times, and per-step token times."""

    def __init__(self, eng, reqs, clients: int):
        from repro.serve import Request

        self.eng, self.reqs, self.Request = eng, reqs, Request
        self.next, self.live = 0, {}  # rid -> request index
        self.sent, self.times, self.idx = {}, {}, {}  # rid -> send time / [(t, count)]
        self.plen = {}
        self.done = {}
        self.steps = []  # (t0, t1, prefill chunks run, decode contexts)
        for _ in range(clients):
            self.send()

    def send(self) -> None:
        prompt, n_out = self.reqs[self.next % len(self.reqs)]
        rid = self.eng.submit(self.Request(prompt=prompt, max_new_tokens=n_out))
        self.idx[rid] = self.next % len(self.reqs)
        self.plen[rid] = gen.pad_len(len(prompt), self.eng.block_size)
        self.next += 1
        self.sent[rid] = time.perf_counter()
        self.live[rid] = 0
        self.times[rid] = []

    def step(self) -> float:
        eng = self.eng
        chunks = eng.stats.prefill_chunks
        t0 = time.perf_counter()
        eng.step()
        t1 = time.perf_counter()
        toks = eng.sched._tokens  # per-request emitted tokens (the scheduler's record)
        contexts, finished = [], []
        for rid, had in self.live.items():
            n = len(toks[rid])
            if n > had:
                self.times[rid].append((t1, n - had))
                self.live[rid] = n
                if n >= 2:  # a decode lane: it wrote token n-1 and read plen + n - 1
                    contexts.append(self.plen[rid] + n - 1)
            if rid in eng.sched._done:
                finished.append(rid)
        for rid in finished:
            self.done[rid] = t1
            del self.live[rid]
            self.send()
        self.steps.append((t0, t1, eng.stats.prefill_chunks - chunks, contexts))
        return t1


def _sharing_prompts(plen: int, block: int, vocab: int, rng) -> list[np.ndarray]:
    """A leader of ``plen`` tokens, followers sharing 1 .. n-1 of its leading
    blocks, and a full repeat: every prefill program a prompt of this padded
    length can reach."""
    lead = rng.integers(1, vocab, size=plen, dtype=np.int32)
    out = [lead]
    for s in range(1, plen // block):
        tail = rng.integers(1, vocab, size=plen - s * block, dtype=np.int32)
        out.append(np.concatenate([lead[: s * block], tail]))
    out.append(lead.copy())
    return out


def warm(eng, mix: dict, block: int, vocab: int, slots: int) -> None:
    from repro.serve import Request

    rng = np.random.default_rng(0)
    for plen in gen.padded_lengths(mix, block):
        lead, *rest = _sharing_prompts(plen, block, vocab, rng)
        eng.submit(Request(prompt=lead, max_new_tokens=2))
        eng.drain()
        for p in rest:
            eng.submit(Request(prompt=p, max_new_tokens=2))
            eng.step()  # one at a time: each follower finds the leader's chain
        eng.drain()
    # slot buckets: grow one request at a time, then shrink as they finish
    for i in range(slots):
        eng.submit(Request(prompt=rng.integers(1, vocab, size=block, dtype=np.int32),
                           max_new_tokens=4 + 3 * i))
        eng.step()
    eng.drain()


def run(cell: harness.Cell, t_start: float, control: bool = False) -> dict:
    from repro.configs import get_config
    from repro.obs.trace import Tracer
    from repro.serve import ServeEngine

    c, mix, sp = cell.config, cell.traffic, cell.spec
    ref = harness.load_reference(cell)
    dims = ref.dims_of(c)
    cfg = get_config(c["arch"]).replace(**c["program"])
    eng_kw = c["engine"]
    counter = harness.CompileCounter()
    weights = ref.make_weights(dims, harness.key_of(cell.seed), c["program"]["param_dtype"])
    spans = Tracer(jax_annotate=True) if cell.trace else None
    origin = time.perf_counter()  # the tracer's ts 0, to within microseconds
    eng = ServeEngine(cfg, weights, max_slots=eng_kw["slots"], max_seq=eng_kw["max_seq"],
                      prompt_granule=eng_kw["block"], block_size=eng_kw["block"],
                      pool_blocks=eng_kw["pool_blocks"], prefill_chunk=eng_kw["prefill_chunk"],
                      attn_impl=cfg.attn_impl, tracer=spans)
    del weights
    reqs = gen.requests(mix, dims["vocab_size"], cell.seed)
    warm(eng, mix, eng_kw["block"], dims["vocab_size"], eng_kw["slots"])

    loop = Loop(eng, reqs, mix["clients"])
    t_ramp = time.perf_counter() + mix["ramp_s"]
    while loop.step() < t_ramp:
        pass
    compiles0 = counter.count
    n_steps0 = len(loop.steps)
    t0 = time.perf_counter()
    t_end = t0 + cell.seconds
    t1 = t0
    while t1 < t_end:
        t1 = loop.step()
    window_steps = loop.steps[n_steps0:]
    window_compiles = counter.count - compiles0

    rec = {"setup_s": t0 - t_start, "window_s": t1 - t0, "window_compiles": window_compiles,
           "dims": dims, "peak": cell.peak, "chips": len(cell.devices), "block": eng_kw["block"],
           "counts": harness.counts_name(c)}
    tokens, ttft, itl = 0, [], []
    for rid, ts in loop.times.items():
        prev = None
        for t, n in ts:
            if t0 < t <= t1:
                tokens += n
                if prev is None:
                    ttft.append(t - loop.sent[rid])
                else:
                    itl.append(t - prev)
                    itl.extend([0.0] * (n - 1))
            prev = t
    rec.update(serve_tokens=tokens, ttft_s=ttft, itl_s=itl)
    rec["decode_only_step_s"] = [b - a for a, b, ch, ctx in window_steps if ch == 0 and ctx]
    rec["attempted"] = sum(1 for rid in loop.sent if loop.sent[rid] <= t1)
    finished = [rid for rid, t in loop.done.items() if t0 < t <= t1]
    rec["failed"] = sum(1 for rid in finished
                        if len(eng.sched._tokens[rid]) != reqs[loop.idx[rid]][1])
    rec["finished"] = len(finished)
    if spans is not None:
        events = _spans(spans, origin, t0, t1)
        rec["step_work"] = _step_work(window_steps, events)
        rec["prefix"] = _prefix_share(events, loop, reqs, eng_kw["block"])
        with harness.profiled(cell) as tr:
            ts0 = len(loop.steps)
            t_stop = time.perf_counter() + sp["trace_seconds"]
            while loop.step() < t_stop:
                pass
        rec["trace"] = tr
        rec["traced_decode_contexts"] = [ctx for _, _, _, ctx in loop.steps[ts0:]]

    rec["memory_peak_bytes"] = harness.memory_peak_bytes(cell.devices)
    served = {rid: (reqs[loop.idx[rid]][0], np.asarray(eng.sched._tokens[rid]))
              for rid in finished}
    del eng, loop
    gc.collect()
    got = check(cell, ref, dims, served, eng_kw["block"], sp, control=control)
    rec["checked"] = got
    rec["checks"] = [{"name": "served_token_gap", "limit": cell.limits["served_token_gap"],
                      "value": got["served_token_gap"]}]
    return rec


def _spans(tracer, origin: float, t0: float, t1: float) -> list:
    """The engine's spans that started in the window: (name, start on the
    ``perf_counter`` clock, args)."""
    out = []
    for ev in tracer.events:
        if ev["ph"] in ("X", "i"):
            s = origin + ev["ts"] * 1e-6
            if t0 <= s <= t1:
                out.append((ev["name"], s, ev["args"]))
    return out


def _step_work(steps, events) -> list:
    """Per window step: the decode contexts and the prefill chunks
    (chunk, prior positions) it ran, from the engine's own spans."""
    chunks = sorted((s, a["chunk"], a["off"]) for name, s, a in events
                    if name == "prefill_chunk")
    out, j = [], 0
    for a, b, _, ctx in steps:
        mine = []
        while j < len(chunks) and chunks[j][0] <= b:
            if chunks[j][0] >= a:
                mine.append((chunks[j][1], chunks[j][2]))
            j += 1
        out.append({"contexts": ctx, "chunks": mine})
    return out


def _prefix_share(events, loop, reqs, block) -> dict:
    """Real prompt tokens admitted in the window, and those of them served
    from adopted blocks (pad positions excluded)."""
    real = adopted = 0
    for name, s, a in events:
        if name != "admit" or a["rid"] not in loop.idx:
            continue
        n_real = len(reqs[loop.idx[a["rid"]]][0])
        pad = a["prompt_len"] - n_real
        real += n_real
        adopted += max(0, a["shared"] * block - pad)
    return {"real": real, "adopted": adopted}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def sample(served: dict, n: int, seed: int) -> list:
    """Up to ``n`` finished requests: the one with the most served tokens,
    then others drawn from the seed."""
    rids = sorted(served)
    if not rids:
        return []
    longest = max(rids, key=lambda r: len(served[r][1]))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng(seed)
    pick = list(rng.choice(rest, size=min(n - 1, len(rest)), replace=False)) if rest else []
    return [longest] + [int(r) for r in pick]


def sequences(served: dict, rids, block: int, span: int):
    """Each request as the engine ran it: the prompt left-padded to its
    bucket, then the served tokens but the last; and where its served
    tokens start."""
    out = []
    for rid in rids:
        prompt, toks = served[rid]
        plen = gen.pad_len(len(prompt), block)
        seq = np.zeros(span, np.int32)
        seq[plen - len(prompt):plen] = prompt
        seq[plen:plen + len(toks) - 1] = toks[:-1]
        out.append((seq, plen, toks))
    return out


def check(cell, ref, dims, served, block, sp, *, control: bool = False) -> dict:
    """Widest gap by which a served token's logit lies below the best of the
    reference module ``ref`` (and, with ``control``, the same for the token
    the float8 control puts first)."""
    weights = ref.make_weights(dims, harness.key_of(cell.seed),
                               cell.config["program"]["param_dtype"])
    fwd = jax.jit(lambda w, t, prec: ref.logits(dims, w, t[None], prec)[0],
                  static_argnums=2)
    gap, gap_ctl, n_tok = 0.0, 0.0, 0
    for seq, plen, toks in sequences(served, sample(served, sp["check_requests"], cell.seed),
                                     block, sp["check_span"]):
        want = np.asarray(fwd(weights, jnp.asarray(seq), "f32")[plen - 1:plen - 1 + len(toks)])
        best = want.max(-1)
        gap = max(gap, float(np.max(best - want[np.arange(len(toks)), toks])))
        n_tok += len(toks)
        if control:
            low = np.asarray(fwd(weights, jnp.asarray(seq), "fp8")[plen - 1:plen - 1 + len(toks)])
            first = low.argmax(-1)
            gap_ctl = max(gap_ctl, float(np.max(best - want[np.arange(len(toks)), first])))
    out = {"served_token_gap": gap, "checked_tokens": n_tok}
    if control:
        out["control_gap"] = gap_ctl
    return out
