"""repro.obs: the unified telemetry path.

Pins, in order: the null sinks are strict no-ops (shared span singleton, no
validation, no writes); the metrics registry semantics and the
EngineStats/ServeStats legacy surface (every scalar field is an emitting
view over the process registry — the equivalence tests here are what let
benches keep reading ``stats.compiles``); the Chrome-trace export schema
(``SCHEMA_VERSION``, event shape, per-thread span nesting); the run-log
schema (typed-event validation, NaN scrubbing, version gate) and the
monitor's reconstruction of the batch/rung/lr schedule from it
(record-for-record against ``AdaptationProgram.history``); the serve-side
span/event stream; and the overhead guard — a disabled tracer adds zero
device-to-host transfers and a bounded sliver of a step to the hot loop.
"""

import json
import math
import threading
import time

import jax
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.configs.base import ModelConfig
from repro.core import AdaptiveBatchController, make_policy
from repro.data import sigmoid_synthetic
from repro.elastic import MeshLadder
from repro.launch import monitor
from repro.models import small
from repro.models import transformer as tf
from repro.obs import from_cli, metrics, runlog, trace
from repro.obs.runlog import RunLog, read_runlog
from repro.obs.trace import Tracer
from repro.optim import sgd
from repro.serve import Request, ServeEngine
from repro.train.engine import EngineStats
from repro.train.loop import ModelFns, Trainer


def _logreg_trainer(train, val, *, m0=16, m_max=256, elastic=None, **kw):
    ctrl = AdaptiveBatchController(
        make_policy("divebatch", m0=m0, m_max=m_max, delta=0.5,
                    dataset_size=len(train), granule=16),
        base_lr=1.0,
    )
    fns = ModelFns(small.logreg_batch_loss, small.logreg_loss,
                   lambda p, b: {"acc": small.logreg_accuracy(p, b)})
    d = train.arrays["x"].shape[1]
    return Trainer(fns, small.logreg_init(jax.random.key(0), d),
                   sgd(momentum=0.9), ctrl, train, val, estimator="exact",
                   elastic=elastic, **kw)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One fully-instrumented elastic training run, shared by the schema /
    reconstruction tests: batch growth forces a real rung transition, the
    checkpoint cadence and an injected event exercise their event kinds."""
    run_dir = str(tmp_path_factory.mktemp("obs_run"))
    train, val, _ = sigmoid_synthetic(n=1000, d=16, seed=0)
    tracer = Tracer()
    log = RunLog(run_dir, meta={"cmd": "test", "task": "sigmoid"})
    t = _logreg_trainer(
        train, val, elastic=MeshLadder(granule=16), tracer=tracer, runlog=log,
        ckpt=CheckpointManager(str(tmp_path_factory.mktemp("obs_ckpt"))),
        ckpt_every=2,
    )
    t.inject_event("probe")
    t.run(4, verbose=False)
    tracer.save(run_dir)
    log.close()
    return t, tracer, run_dir


# ---------------------------------------------------------------------------
# null sinks


class TestNullSinks:
    def test_null_tracer_is_strict_noop(self):
        tr = trace.NULL
        assert tr.enabled is False
        # one shared stateless span object — no allocation per call
        assert tr.span("a", x=1) is tr.span("b")
        with tr.span("a", x=1) as s:
            assert s is trace.NULL.span("c")
        assert tr.instant("x", y=2) is None
        assert tr.save("/nonexistent/dir") is None
        doc = tr.to_json()
        assert doc["traceEvents"] == []
        assert doc["otherData"]["schema"] == trace.SCHEMA_VERSION

    def test_null_runlog_skips_validation(self):
        # the disabled sink must not pay (or raise on) kind validation
        assert runlog.NULL.enabled is False
        assert runlog.NULL.emit("definitely_not_a_kind") is None
        assert runlog.NULL.emit("epoch") is None  # missing fields: still ok
        assert runlog.NULL.close() is None


# ---------------------------------------------------------------------------
# metrics registry + stats equivalence


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = metrics.Registry()
        c = reg.counter("a.steps")
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert reg.counter("a.steps") is c  # get-or-create
        g = reg.gauge("a.wall")
        g.set(1.5)
        assert g.value == 1.5
        h = reg.histogram("a.lat")
        for v in (1.0, 3.0, 2.0):
            h.observe(v)
        assert (h.count, h.total, h.vmin, h.vmax, h.last) == (3, 6.0, 1.0, 3.0, 2.0)
        assert h.mean == 2.0
        snap = reg.snapshot()
        assert snap["a.steps"] == 5 and snap["a.wall"] == 1.5
        assert snap["a.lat"]["count"] == 3  # histograms expand to summaries

    def test_type_conflict_raises(self):
        reg = metrics.Registry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")

    def test_unique_namespaces(self):
        reg = metrics.Registry()
        assert reg.unique_namespace("train.engine") != reg.unique_namespace(
            "train.engine")


class TestStatsViews:
    # the legacy dict surface, pinned key-for-key so no bench/test consumer
    # silently loses a field when the registry backing evolves
    ENGINE_KEYS = [
        "compiles", "bucket_hits", "bucket_misses", "steps", "compile_s",
        "reshards", "dispatch_wall_s", "donate", "buckets", "rungs", "tiers",
        "dispatch_steps_per_sec",
    ]
    SERVE_KEYS = [
        "compiles", "bucket_hits", "bucket_misses", "prefill_compiles",
        "aux_compiles", "steps", "slot_steps", "tokens", "prefills",
        "prefill_chunks", "shared_prefill_hits", "shared_blocks",
        "cow_copies", "pool_blocks", "peak_blocks", "block_size", "retired",
        "reshards", "resizes", "compile_s", "tokens_per_sec", "donate",
        "buckets", "rungs",
    ]

    def test_engine_stats_registry_equivalence(self):
        reg = metrics.Registry()
        st = EngineStats(donate=False, registry=reg)
        st.compiles += 2
        st.steps += 7
        st.compile_s += 0.25
        st.buckets.append(64)  # plain attribute, not registry-backed
        assert st.as_dict() == dict(
            compiles=2, bucket_hits=0, bucket_misses=0, steps=7,
            compile_s=0.25, reshards=0, dispatch_wall_s=0, donate=False,
            buckets=[64], rungs=[], tiers=[], dispatch_steps_per_sec=0.0,
        )
        snap = reg.snapshot()
        for f in (*st._COUNTERS, *st._GAUGES):
            assert snap[f"{st.namespace}.{f}"] == getattr(st, f), f

    def test_as_dict_keys_pinned(self):
        from repro.serve.engine import ServeStats
        assert list(EngineStats(registry=metrics.Registry()).as_dict()) \
            == self.ENGINE_KEYS
        assert list(ServeStats(registry=metrics.Registry()).as_dict()) \
            == self.SERVE_KEYS

    def test_live_engine_emits_into_process_registry(self, traced_run):
        t, _, _ = traced_run
        st = t.engine.stats
        snap = metrics.REGISTRY.snapshot()
        assert st.namespace.startswith("train.engine.")
        for f in (*st._COUNTERS, *st._GAUGES):
            assert snap[f"{st.namespace}.{f}"] == getattr(st, f), f
        assert st.steps > 0 and st.compiles > 0

    def test_two_engines_never_collide(self):
        a = EngineStats(registry=metrics.REGISTRY)
        b = EngineStats(registry=metrics.REGISTRY)
        a.steps += 3
        assert b.steps == 0 and a.namespace != b.namespace


# ---------------------------------------------------------------------------
# trace schema


class TestTraceSchema:
    def test_schema_version_pinned(self):
        assert trace.SCHEMA_VERSION == 1
        assert runlog.SCHEMA_VERSION == 1

    def test_export_shape(self, traced_run):
        _, tracer, _ = traced_run
        doc = tracer.to_json()
        assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
        assert doc["displayTimeUnit"] == "ms"
        other = doc["otherData"]
        assert other["schema"] == trace.SCHEMA_VERSION
        assert isinstance(other["wall_origin"], float)
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("X", "i", "M")
            assert {"name", "ts", "pid", "tid"} <= set(ev)
            if ev["ph"] == "X":
                assert ev["dur"] > 0
            if ev["ph"] == "i":
                assert ev["s"] == "t"

    def test_span_taxonomy(self, traced_run):
        t, tracer, _ = traced_run
        names = {e["name"] for e in tracer.events if e["ph"] == "X"}
        assert {"compile", "dispatch", "observe", "epoch"} <= names
        # batch growth m0=16 -> m_max crosses ladder rungs: the transition
        # must be visible as a reshard span AND in the engine stats
        assert "reshard" in names
        assert t.engine.stats.reshards > 0
        dispatch = [e for e in tracer.events if e["name"] == "dispatch"]
        assert len(dispatch) == t.engine.stats.steps
        assert all("bucket" in e["args"] and "step_num" in e["args"]
                   for e in dispatch)

    def test_spans_nest_per_thread(self, traced_run):
        _, tracer, _ = traced_run
        by_tid = {}
        for ev in tracer.events:
            if ev["ph"] == "X":
                by_tid.setdefault(ev["tid"], []).append(ev)
        eps = 0.01  # µs; absorbs the 1ns min-duration clamp
        for evs in by_tid.values():
            evs.sort(key=lambda e: (e["ts"], -e["dur"]))
            stack = []  # end timestamps of open ancestors
            for ev in evs:
                t0, t1 = ev["ts"], ev["ts"] + ev["dur"]
                while stack and t0 >= stack[-1] - eps:
                    stack.pop()
                if stack:  # inside an ancestor: must end before it does
                    assert t1 <= stack[-1] + eps, (ev, stack)
                stack.append(t1)

    def test_save_roundtrip(self, tmp_path):
        tr = Tracer()
        with tr.span("outer", k="v"):
            with tr.span("inner"):
                pass
        tr.instant("mark", n=np.int64(3))  # numpy scalars must serialize
        path = tr.save(str(tmp_path))  # directory -> <dir>/trace.json
        assert path == str(tmp_path / "trace.json")
        doc = json.loads((tmp_path / "trace.json").read_text())
        names = [e["name"] for e in doc["traceEvents"] if e["name"] != "gc"]
        # inner exits first; one thread_name metadata record per thread
        assert names == ["thread_name", "inner", "outer", "mark"]
        assert doc["traceEvents"][0]["args"]["name"] == \
            threading.current_thread().name

    def test_origin_maps_onto_perf_counter(self):
        tr = Tracer()
        before = time.perf_counter()
        with tr.span("s"):
            pass
        after = time.perf_counter()
        ev = next(e for e in tr.events if e["name"] == "s")
        start = (tr.origin_ns + ev["ts"] * 1e3) * 1e-9
        assert before - 1e-3 <= start <= after + 1e-3
        assert abs(start - before) < 1e-3
        other = tr.to_json()["otherData"]
        assert other["perf_counter_origin_ns"] == tr.origin_ns

    def test_set_adds_args_at_exit(self):
        tr = Tracer()
        with tr.span("s", a=1) as sp:
            sp.set(b=2)
        assert tr.events[-1]["args"] == {"a": 1, "b": 2}

    def test_only_the_outermost_step_span_marks_a_profiler_step(self):
        """Spans tagged ``step_num`` inside an open step become plain
        annotations, so the profiler sees one step per engine step."""
        made = []

        class Ann:
            def __init__(self, kind, name):
                made.append((kind, name))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        class FakeProfiler:
            def StepTraceAnnotation(self, name, step_num):
                return Ann("step", name)

            def TraceAnnotation(self, name):
                return Ann("plain", name)

        tr = Tracer()
        tr._profiler = FakeProfiler()
        for i in range(2):
            with tr.span("serve_step", step_num=i):
                with tr.span("observe", step_num=i):
                    pass
        assert made == [("step", "serve_step"), ("plain", "observe")] * 2

    def test_collections_are_gc_spans(self):
        import gc
        hooks = len(gc.callbacks)
        tr = Tracer()
        assert len(gc.callbacks) == hooks + 1
        gc.collect()
        evs = [e for e in tr.events if e["name"] == "gc"]
        assert evs and evs[-1]["args"]["generation"] == 2
        assert evs[-1]["args"]["collected"] >= 0 and evs[-1]["dur"] > 0
        del tr, evs
        assert len(gc.callbacks) == hooks  # a dead tracer's hook is gone

    def test_collection_under_the_tracer_lock_does_not_deadlock(self):
        """A collection can start while the recording thread holds the
        lock (inside an append); it is recorded, not a deadlock."""
        import gc
        tr = Tracer()
        done = []

        def work():
            with tr._lock:
                gc.collect()
            done.append(True)

        th = threading.Thread(target=work, daemon=True)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive() and done
        assert any(e["name"] == "gc" for e in tr.events)

    def test_threads_get_own_lanes(self):
        tr = Tracer()
        def work():
            with tr.span("bg"):
                pass
        th = threading.Thread(target=work, name="bg-thread")
        th.start()
        th.join()
        with tr.span("fg"):
            pass
        evs = tr.events
        # (a collection is recorded on whichever thread it starts in)
        tids = {e["tid"] for e in evs if e["ph"] == "X" and e["name"] != "gc"}
        assert len(tids) == 2
        meta = [e for e in evs if e["ph"] == "M"]
        assert {m["args"]["name"] for m in meta} >= {"bg-thread"}


# ---------------------------------------------------------------------------
# run log


class TestRunLog:
    def test_emit_validation(self, tmp_path):
        with RunLog(str(tmp_path)) as log:
            with pytest.raises(ValueError, match="unknown run-log event kind"):
                log.emit("nope", a=1)
            with pytest.raises(ValueError, match="missing required fields"):
                log.emit("epoch", epoch=0)
            with pytest.raises(ValueError, match="reserved"):
                log.emit("inject", name="x", kind="boom")
            with pytest.raises(ValueError, match="reserved"):
                log.emit("inject", name="x", t=0.0)

    def test_roundtrip_and_nan_scrub(self, tmp_path):
        with RunLog(str(tmp_path), meta={"seed": 3}) as log:
            log.emit("epoch", epoch=0, steps=5, batch_size=64, lr=0.5,
                     loss=float("nan"), gns=math.inf, diversity=np.float32(0.5))
            log.emit("checkpoint", epoch=0, step=5)
        evs = read_runlog(str(tmp_path))  # directory or file path both work
        assert [e["kind"] for e in evs] == ["run_start", "epoch", "checkpoint"]
        assert all(e["v"] == runlog.SCHEMA_VERSION for e in evs)
        assert evs[0]["run"] == {"seed": 3}
        ep = evs[1]
        assert ep["loss"] is None and ep["gns"] is None  # non-finite -> null
        assert ep["diversity"] == 0.5  # numpy scalar -> plain float
        assert evs[0]["t"] <= ep["t"] <= evs[2]["t"]

    def test_reader_rejects_newer_schema(self, tmp_path):
        p = tmp_path / "runlog.jsonl"
        p.write_text(json.dumps({"v": runlog.SCHEMA_VERSION + 1,
                                 "kind": "epoch", "t": 0.0}) + "\n")
        with pytest.raises(ValueError, match="newer"):
            read_runlog(str(p))

    def test_emit_after_close_is_dropped(self, tmp_path):
        log = RunLog(str(tmp_path))
        log.close()
        log.emit("inject", name="late")  # validated, silently dropped
        assert [e["kind"] for e in read_runlog(str(tmp_path))] == ["run_start"]

    def test_from_cli(self, tmp_path):
        assert from_cli(None, None) == (None, None)
        tr, log = from_cli(str(tmp_path / "run"), "")  # "" = into trace dir
        assert tr.enabled and log.path.endswith("runlog.jsonl")
        log.close()
        with pytest.raises(ValueError):
            from_cli(None, "")


# ---------------------------------------------------------------------------
# monitor reconstruction


class TestMonitor:
    def test_schedule_mirrors_program_history(self, traced_run):
        t, _, run_dir = traced_run
        sched = monitor.schedule(monitor.load(run_dir))
        hist = t.adapt.history
        assert len(sched) == len(hist) > 0
        for row, ap in zip(sched, hist):
            assert (row["epoch"], row["step"], row["boundary"],
                    row["batch_size"]) == (ap.epoch, ap.step, ap.boundary,
                                           ap.batch_size)
            assert row["lr"] == pytest.approx(ap.lr)
        # the rung transition is reconstructable from the same file: rows
        # after the reshard carry its destination rung
        assert sched[-1]["rung"] is not None

    def test_event_stream_shape(self, traced_run):
        t, _, run_dir = traced_run
        evs = monitor.load(run_dir)
        kinds = [e["kind"] for e in evs]
        assert kinds[0] == "run_start"
        assert kinds.count("epoch") == len(t.history)
        assert kinds.count("checkpoint") >= 1  # ckpt_every=2 over 4 epochs
        assert "inject" in kinds and "compile" in kinds
        reshards = [e for e in evs if e["kind"] == "reshard"]
        assert len(reshards) == t.engine.stats.reshards
        assert all(e["scope"] == "train" for e in reshards)
        # decision events carry the full Applied record
        dec = next(e for e in evs if e["kind"] == "decision")
        assert {"reason", "estimator", "raw_batch_size", "rescaled"} <= set(dec)

    def test_summary_and_tables(self, traced_run):
        _, _, run_dir = traced_run
        text = monitor.summary(monitor.load(run_dir))
        assert "epochs:" in text and "schedule (" in text
        assert "reshard   [train]" in text
        assert "inject    'probe'" in text

    def test_follow_drain_holds_back_torn_lines(self, tmp_path):
        """--follow must never emit (or json-parse) a half-written trailing
        record: a line flushed mid-write stays in the carry buffer and is
        re-read whole once the writer completes it."""
        rec1 = json.dumps({"v": 1, "kind": "epoch", "epoch": 0})
        rec2 = json.dumps({"v": 1, "kind": "demote", "src": 3, "dst": 2})
        path = tmp_path / "runlog.jsonl"
        with open(path, "w") as w:
            w.write(rec1 + "\n" + rec2[:10])  # torn mid-record
            w.flush()
            with open(path) as r:
                lines, buf = monitor._drain(r, "")
                assert lines == [rec1]  # the torn tail is NOT emitted
                assert buf == rec2[:10]
                # a second poll before the writer finishes yields nothing
                lines2, buf = monitor._drain(r, buf)
                assert lines2 == [] and buf == rec2[:10]
                # writer completes the record: the follower re-reads it whole
                w.write(rec2[10:] + "\n")
                w.flush()
                lines3, buf = monitor._drain(r, buf)
                assert lines3 == [rec2] and buf == ""
                assert [json.loads(l) for l in [*lines, *lines3]] == [
                    {"v": 1, "kind": "epoch", "epoch": 0},
                    {"v": 1, "kind": "demote", "src": 3, "dst": 2}]

    def test_drain_skips_blank_lines(self, tmp_path):
        path = tmp_path / "log.jsonl"
        path.write_text("a\n\n   \nb\n")
        with open(path) as r:
            lines, buf = monitor._drain(r, "")
        assert lines == ["a", "b"] and buf == ""

    def test_merge_traces(self, traced_run, tmp_path):
        _, tracer, run_dir = traced_run
        out = str(tmp_path / "merged.json")
        monitor.merge_traces(run_dir, out)
        doc = json.loads(open(out).read())
        evs = doc["traceEvents"]
        # all tracer events + one runlog lane (thread_name + one instant per
        # logged event), aligned via wall_origin
        lane = [e for e in evs if e["tid"] == -1]
        # the saved trace: a live tracer goes on recording collections
        saved = json.loads(open(f"{run_dir}/trace.json").read())["traceEvents"]
        assert len(evs) == len(saved) + len(lane)
        assert lane[0]["args"]["name"] == "runlog"
        assert len(lane) == 1 + len(monitor.load(run_dir))
        assert all(e["ph"] == "i" for e in lane[1:])


# ---------------------------------------------------------------------------
# serve instrumentation


def _serve_cfg():
    return ModelConfig(
        name="t", family="dense", num_layers=2, d_model=32, num_heads=4,
        num_kv_heads=2, d_ff=64, vocab_size=61, pattern=("attn",),
        param_dtype="float32", compute_dtype="float32", xent_chunk=8,
        remat=False,
    )


def _serve_requests(seed, lens=(20, 27, 12), new=(8, 6, 8)):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(1, 61, size=n).astype(np.int32),
                    max_new_tokens=m) for n, m in zip(lens, new)]


def _inside(child, parent):
    return (child["tid"] == parent["tid"] and child["ts"] >= parent["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 0.01)


class TestServeObs:
    def test_serve_spans_and_events(self, tmp_path):
        cfg = _serve_cfg()
        params = tf.init_params(cfg, jax.random.key(0))
        reqs = _serve_requests(7)
        tracer = Tracer()
        log = RunLog(str(tmp_path))
        eng = ServeEngine(cfg, params, max_slots=4, max_seq=64,
                          prompt_granule=8, prefill_chunk=8,
                          tracer=tracer, runlog=log, obs_window=4)
        outs = eng.generate(reqs)
        log.close()
        assert all(len(o.tokens) for o in outs)

        spans = [e["name"] for e in tracer.events if e["ph"] == "X"]
        assert {"admit", "prefill_chunk", "decode", "compile"} <= set(spans)
        assert spans.count("prefill_chunk") == eng.stats.prefill_chunks
        assert spans.count("decode") == eng.stats.steps
        # one serve_step span per boundary that decoded, around one token read
        steps = [e for e in tracer.events if e["name"] == "serve_step"]
        reads = [e for e in tracer.events if e["name"] == "token_read"]
        decoding = [s for s in steps if s["args"]["live"] > 0]
        assert len(decoding) == eng.stats.steps == len(reads)
        assert [s["args"]["step_num"] for s in decoding] == \
            list(range(eng.stats.steps))
        for s in steps:
            mine = [r for r in reads if _inside(r, s)]
            assert len(mine) == (1 if s["args"]["live"] else 0), s
        assert sum(s["args"]["prefill_chunks"] for s in steps) == \
            eng.stats.prefill_chunks
        # the profiler's step is the serve_step, not the decode inside it
        assert all("step_num" not in e["args"] for e in tracer.events
                   if e["name"] == "decode")

        evs = read_runlog(str(tmp_path))
        kinds = [e["kind"] for e in evs]
        assert kinds.count("serve_admit") == 3
        assert kinds.count("serve_retire") == 3
        assert kinds.count("serve_window") >= 1
        # the default FifoPolicy decides the identity: nothing to mirror
        assert kinds.count("serve_policy") == 0
        compiles = [e for e in evs if e["kind"] == "compile"]
        assert compiles and all(e["scope"] == "serve" for e in compiles)
        assert {c["exe_kind"] for c in compiles} >= {"decode", "prefill"}
        admit = next(e for e in evs if e["kind"] == "serve_admit")
        assert admit["prompt_len"] > 0 and admit["budget"] > 0
        win = next(e for e in evs if e["kind"] == "serve_window")
        assert win["tokens"] > 0 and "tokens_per_sec" in win
        # serve table renders from the same stream
        assert "tokens_per_sec" in monitor.serve_table(evs)

        st = eng.stats
        snap = metrics.REGISTRY.snapshot()
        assert st.namespace.startswith("serve.engine.")
        for f in (*st._COUNTERS, *st._GAUGES):
            assert snap[f"{st.namespace}.{f}"] == getattr(st, f), f

    def test_request_lifecycle(self):
        """Every event of one request carries its rid: submit, admit,
        first_token and retire once each, in that order."""
        cfg = _serve_cfg()
        params = tf.init_params(cfg, jax.random.key(0))
        tracer = Tracer()
        eng = ServeEngine(cfg, params, max_slots=2, max_seq=64,
                          prompt_granule=8, prefill_chunk=8, tracer=tracer)
        rids = [eng.submit(r) for r in _serve_requests(9, (20, 27, 12, 20))]
        eng.drain()
        kinds = ("submit", "admit", "first_token", "retire")
        by = {k: [e for e in tracer.events if e["name"] == k] for k in kinds}
        for k in kinds:
            assert sorted(e["args"]["rid"] for e in by[k]) == rids, k
        for rid in rids:
            ts = [next(e["ts"] for e in by[k] if e["args"]["rid"] == rid)
                  for k in kinds]
            assert ts == sorted(ts), (rid, ts)
        chunks = [e for e in tracer.events if e["name"] == "prefill_chunk"]
        assert {e["args"]["rid"] for e in chunks} == set(rids)
        # a finished prompt's first token is a device wait of its own
        assert len([e for e in tracer.events if e["name"] == "prefill_read"]) \
            == len(rids)

    def test_serve_policy_event(self, tmp_path):
        """An applied ServePolicy decision mirrors into the typed
        ``serve_policy`` run-log event, and only when it changed something
        (a reorder here: 6 inverted-priority requests into 2 slots)."""
        cfg = ModelConfig(
            name="t", family="dense", num_layers=2, d_model=32, num_heads=4,
            num_kv_heads=2, d_ff=64, vocab_size=61, pattern=("attn",),
            param_dtype="float32", compute_dtype="float32", xent_chunk=8,
            remat=False,
        )
        params = tf.init_params(cfg, jax.random.key(0))
        rng = np.random.default_rng(8)

        def reqs():
            return [Request(prompt=rng.integers(1, 61, size=4)
                            .astype(np.int32), max_new_tokens=4,
                            tenant=f"t{i % 2}", priority=i)
                    for i in range(6)]

        log = RunLog(str(tmp_path))
        eng = ServeEngine(cfg, params, max_slots=2, max_seq=64,
                          prompt_granule=8, policy="priority", runlog=log)
        eng.generate(reqs())
        log.close()
        evs = read_runlog(str(tmp_path))
        pol = [e for e in evs if e["kind"] == "serve_policy"]
        assert pol  # ascending priorities vs FIFO: a genuine reorder
        for e in pol:
            assert e["reason"] == "priority"
            assert e["step"] >= 0 and e["queue_depth"] > 0
            # emitted ONLY when the decision changed something — here that
            # can only be the reorder (no budget/patience in the decision)
            assert e["reordered"] is True
            assert e["slot_budget"] is None
        # the monitor renders the decision stream as lifecycle lines
        assert "policy    'priority'" in monitor.lifecycle(evs)


# ---------------------------------------------------------------------------
# adaptation instrumentation


class TestAdaptObs:
    def test_read_signals_spans(self):
        from repro.adapt import read_signals
        train, val, _ = sigmoid_synthetic(n=512, d=16, seed=0)
        t = _logreg_trainer(train, val, m0=64, m_max=64)
        batch = jax.tree.map(jax.numpy.asarray, train.get(np.arange(64)))
        state, _ = t.engine.step(t.state, batch, 0.5)
        tr = Tracer()
        sig, state = read_signals(state, "exact", reset=True, batch_size=64,
                                  tracer=tr)
        assert sig.samples == 64
        spans = {e["name"]: e for e in tr.events
                 if e["ph"] == "X" and e["name"] != "gc"}
        assert set(spans) == {"read_signals", "signals_reset",
                              "signals_transfer"}
        for child in ("signals_reset", "signals_transfer"):
            assert _inside(spans[child], spans["read_signals"]), child
        assert spans["signals_reset"]["ts"] < spans["signals_transfer"]["ts"]


# ---------------------------------------------------------------------------
# overhead guard


class TestOverheadGuard:
    def _engine_and_batch(self):
        train, val, _ = sigmoid_synthetic(n=512, d=16, seed=0)
        t = _logreg_trainer(train, val, m0=64, m_max=64)
        batch = jax.tree.map(jax.numpy.asarray, train.get(np.arange(64)))
        return t.engine, t.state, batch

    def test_disabled_tracer_zero_device_to_host_transfers(self):
        """The ISSUE's contract, enforced mechanically: with the default
        (disabled) sinks the engine hot loop performs NO device-to-host
        transfer per step — jax's transfer guard turns any implicit D2H
        into an error.  The enabled tracer holds the same property (spans
        record host-side wall time and python scalars only)."""
        eng, state, batch = self._engine_and_batch()
        assert eng.tracer is trace.NULL and eng.runlog is runlog.NULL
        state, _ = eng.step(state, batch, 0.5)  # warm the compile cache
        with jax.transfer_guard_device_to_host("disallow"):
            for _ in range(3):
                state, _ = eng.step(state, batch, 0.5)
            eng.tracer = Tracer()
            for _ in range(3):
                state, _ = eng.step(state, batch, 0.5)
        assert len([e for e in eng.tracer.events
                    if e["name"] == "dispatch"]) == 3

    def test_serve_step_adds_no_transfer_or_clock_read(self, monkeypatch):
        """Serving's hot loop reads the device only where it means to (each
        read explicit, so the guard turns any other into an error), the same
        reads with the tracer on as off, and with the default sink it reads
        no clock through the engine or the tracer."""
        import types
        from repro.serve import engine as engine_mod
        cfg = _serve_cfg()
        params = tf.init_params(cfg, jax.random.key(0))
        eng = ServeEngine(cfg, params, max_slots=4, max_seq=64,
                          prompt_granule=8, prefill_chunk=8)
        assert eng.tracer is trace.NULL
        eng.generate(_serve_requests(1))  # compile every program first
        reads, clocks = [], []
        get = jax.device_get
        monkeypatch.setattr(jax, "device_get",
                            lambda x: reads.append(1) or get(x))
        for mod in (engine_mod, trace):
            stub = types.SimpleNamespace(
                time=time.time,
                perf_counter=lambda: clocks.append(1) or time.perf_counter(),
                perf_counter_ns=lambda: (clocks.append(1)
                                         or time.perf_counter_ns()))
            monkeypatch.setattr(mod, "time", stub)

        def run(seed):
            del reads[:], clocks[:]
            s0, p0 = eng.stats.steps, eng.stats.prefills
            with jax.transfer_guard_device_to_host("disallow"):
                eng.generate(_serve_requests(seed))
            # one read per decode step, and per finished prompt its first
            # token and the logits kept for full-prompt hits; no other
            assert len(reads) == ((eng.stats.steps - s0)
                                  + 2 * (eng.stats.prefills - p0))
            return len(reads), len(clocks)

        off = run(2)
        assert off[1] == 0
        eng.tracer = Tracer()
        on = run(3)
        assert on[0] == off[0] and on[1] > 0

    def test_disabled_path_cost_is_a_sliver_of_a_step(self):
        """Deterministic micro-ratio (no flaky wall A/B: that lives in
        benchmarks/bench_engine.py as the engine_obs_overhead row): the
        disabled path adds one attribute load + enabled-branch per step,
        measured here against the measured warm step time."""
        eng, state, batch = self._engine_and_batch()
        state, _ = eng.step(state, batch, 0.5)  # compile outside the timing
        walls = []
        for _ in range(10):
            t0 = time.perf_counter()
            state, out = eng.step(state, batch, 0.5)
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        step_s = sorted(walls)[len(walls) // 2]

        n = 100_000
        t0 = time.perf_counter()
        for _ in range(n):
            tr = eng.tracer  # exactly the per-step disabled-path work
            if tr.enabled:
                pass  # pragma: no cover
        per_step = (time.perf_counter() - t0) / n
        assert per_step / step_s < 0.03, (per_step, step_s)
