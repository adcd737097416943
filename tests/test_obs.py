"""Serve-layer observability: tracer/metrics units, Chrome trace export
schema, and end-to-end span/metric consistency through the serve engine.

The e2e tests validate the ISSUE's acceptance contract: a traced serve run
produces a Perfetto-loadable artifact whose spans reconstruct every
request lifecycle (queued -> admitted -> decode -> retired, preemption
re-entries included) and whose metrics agree with the engine's own
bookkeeping (TTFT histogram count == completed requests, decode span time
bounded by wall time, pool gauges drained to idle).
"""
import json
import math
import threading
import time

import numpy as np
import pytest

from repro.obs import (TRACK_ENGINE, Counter, Gauge, Histogram,
                       MetricsRegistry, Observability, StatsLogger, Tracer,
                       chrome_trace_events, env_enabled, export_chrome_trace,
                       from_env, span)


# ------------------------------------------------------------------ tracer
def test_tracer_ring_wrap_keeps_newest_and_counts_dropped():
    tr = Tracer(capacity=8)
    for i in range(11):
        tr.add(f"s{i}", "t", float(i), float(i) + 0.5)
    assert len(tr) == 8
    assert tr.dropped == 3
    names = [s[0] for s in tr.spans()]
    assert names == [f"s{i}" for i in range(3, 11)]  # oldest-first, newest 8
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_disabled_records_nothing():
    tr = Tracer(enabled=False)
    tr.add("a", "t", 0.0, 1.0)
    tr.instant("b", "t")
    with span("c", tr, track="t"):
        pass
    assert len(tr) == 0
    tr.enabled = True           # re-checked per call
    tr.add("a", "t", 0.0, 1.0)
    assert len(tr) == 1


def test_tracer_span_context_and_instant():
    tr = Tracer()
    with span("work", tr, track="t", k=1):
        pass
    tr.instant("mark", "t")
    spans = tr.spans()
    assert [s[0] for s in spans] == ["work", "mark"]
    work, mark = spans
    assert work[3] >= work[2] and work[4] == {"k": 1}
    assert mark[2] == mark[3]   # zero duration == instant
    t0 = tr.t0
    tr.clear()
    assert tr.t0 == t0          # one clock across clears


def test_tracer_thread_safety_no_lost_spans():
    tr = Tracer(capacity=10_000)

    def burst(k):
        for i in range(500):
            tr.add(f"w{k}", "t", 0.0, 1.0)

    threads = [threading.Thread(target=burst, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr) == 2000 and tr.dropped == 0


# ----------------------------------------------------------------- metrics
def test_counter_and_gauge():
    c = Counter("c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    c.reset()
    assert c.value == 0
    g = Gauge("g")
    g.set(7)
    g.inc(-2)
    assert g.value == 5


def test_histogram_exact_percentiles_and_summary():
    h = Histogram("h")
    for i in range(1, 101):                      # 1ms .. 100ms
        h.record(i / 1000.0)
    assert h.count == 100
    assert h.percentile(50) == pytest.approx(0.050)
    assert h.percentile(99) == pytest.approx(0.099)
    assert h.percentile(100) == pytest.approx(0.100)
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == pytest.approx(0.001)
    assert s["max"] == pytest.approx(0.100)
    assert s["mean"] == pytest.approx(sum(range(1, 101)) / 100 / 1000.0)
    assert s["p50"] == pytest.approx(0.050)


def test_histogram_bucket_fallback_beyond_retention():
    h = Histogram("h", keep_samples=10)
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=math.log(0.01), sigma=1.0, size=2000)
    for v in vals:
        h.record(float(v))
    # beyond the retention cap: bucket interpolation, still within one
    # growth factor of the exact percentile (geometric-midpoint bound)
    for q in (50.0, 99.0):
        exact = float(np.percentile(vals, q))
        approx = h.percentile(q)
        assert exact / h.growth <= approx <= exact * h.growth
    assert h.summary()["count"] == 2000


def test_histogram_validation_and_empty():
    with pytest.raises(ValueError):
        Histogram("h", base=0.0)
    h = Histogram("h")
    assert h.percentile(50) == 0.0
    assert h.summary()["count"] == 0
    with pytest.raises(ValueError):
        h.percentile(101)


def test_registry_get_or_create_kind_mismatch_and_inplace_reset():
    reg = MetricsRegistry()
    c = reg.counter("x")
    assert reg.counter("x") is c                 # get-or-create
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")
    h = reg.histogram("lat")
    c.inc(3)
    h.record(0.5)
    snap = reg.snapshot()
    assert snap["x"] == 3 and snap["lat"]["count"] == 1
    reg.reset()
    assert c.value == 0 and h.count == 0         # SAME handles, zeroed
    assert reg.names() == ["lat", "x"]


# ------------------------------------------------------------------ export
def _validate_chrome_trace(payload):
    """The trace-event-schema assertions the ISSUE's acceptance names."""
    assert set(payload) >= {"traceEvents", "displayTimeUnit", "otherData"}
    assert payload["displayTimeUnit"] == "ms"
    assert {"spans", "dropped_spans"} <= set(payload["otherData"])
    events = payload["traceEvents"]
    tracks = {}
    for ev in events:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        assert ev["ph"] in ("M", "X", "i")
        if ev["ph"] == "M":
            if ev["name"] == "thread_name":
                tracks[ev["tid"]] = ev["args"]["name"]
        elif ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] > 0
        else:                                    # instant
            assert ev["s"] == "t" and "dur" not in ev
    n_spans = sum(ev["ph"] in ("X", "i") for ev in events)
    assert n_spans == payload["otherData"]["spans"]
    # every span event rides a named track
    for ev in events:
        if ev["ph"] in ("X", "i"):
            assert ev["tid"] in tracks
    return tracks


def test_export_chrome_trace_schema(tmp_path):
    tr = Tracer()
    t0 = tr.t0
    tr.add("cycle", TRACK_ENGINE, t0 + 0.001, t0 + 0.002)
    tr.add("decode", "slot0", t0 + 0.001, t0 + 0.003, {"req": 1})
    tr.add("decode", "slot10", t0 + 0.002, t0 + 0.004)
    tr.add("decode", "slot2", t0 + 0.002, t0 + 0.004)
    tr.instant("retired", "slot0", t0 + 0.005, {"req": 1})
    reg = MetricsRegistry()
    reg.counter("serve.tokens_out").inc(42)
    path = str(tmp_path / "trace.json")
    export_chrome_trace(path, tr, reg)
    payload = json.loads(open(path).read())
    tracks = _validate_chrome_trace(payload)
    assert payload["otherData"]["metrics"]["serve.tokens_out"] == 42
    # engine track first, then natural (slot2 < slot10) order
    ordered = [tracks[tid] for tid in sorted(tracks)]
    assert ordered == [TRACK_ENGINE, "slot0", "slot2", "slot10"]
    # args survive the round trip
    ev = next(e for e in payload["traceEvents"]
              if e["ph"] == "X" and e["args"].get("req") == 1)
    assert ev["name"] == "decode"


def test_stats_logger_line_and_thread():
    reg = MetricsRegistry()
    tok = reg.counter("serve.tokens_out")
    reg.gauge("serve.queue_depth").set(3)
    reg.histogram("serve.ttft_s").record(0.25)
    lines = []
    logger = StatsLogger(reg, interval=0.05, emit=lines.append)
    tok.inc(100)
    line = logger.line()
    assert "tok/s" in line and "queue 3" in line and "ttft_p50 250ms" in line
    logger.start()
    with pytest.raises(RuntimeError, match="already started"):
        logger.start()
    tok.inc(50)
    time.sleep(0.2)
    logger.stop()
    assert lines, "logger thread emitted nothing"
    logger.stop()                                # idempotent
    with pytest.raises(ValueError):
        StatsLogger(reg, interval=0.0)


def test_observability_bundle_and_env(tmp_path, monkeypatch):
    obs = Observability(trace_capacity=16)
    t0 = obs.tracer.t0
    obs.tracer.add("a", "t", t0 + 0.1, t0 + 0.2)
    obs.metrics.counter("c").inc()
    path = obs.export(str(tmp_path / "t.json"))
    _validate_chrome_trace(json.loads(open(path).read()))
    obs.reset()
    assert len(obs.tracer) == 0 and obs.metrics.snapshot()["c"] == 0

    assert env_enabled("1") and env_enabled("TRUE") and env_enabled(" on ")
    assert not env_enabled("") and not env_enabled("0")
    monkeypatch.delenv("REPRO_OBS", raising=False)
    assert from_env() is None
    monkeypatch.setenv("REPRO_OBS", "1")
    assert isinstance(from_env(), Observability)


# ------------------------------------------------- ServeRequest lifecycle
def test_serve_request_timestamps_and_timeout_message():
    from repro.serve.scheduler import ServeRequest

    req = ServeRequest(np.arange(1, 5, dtype=np.int32), 4)
    assert req.ttft is None and req.queue_wait is None
    with pytest.raises(TimeoutError) as ei:
        req.result(timeout=0.01)
    msg = str(ei.value)
    assert "submitted_at=unset" in msg and "preempted 0x" in msg
    req.submitted_at = 10.0
    req.admitted_at = 10.5
    req.first_token_at = 11.0
    req.finished_at = 12.0
    assert req.queue_wait == pytest.approx(0.5)
    assert req.ttft == pytest.approx(1.0)
    with pytest.raises(TimeoutError) as ei:
        req.result(timeout=0.01)
    assert "first_token_at=11.000" in str(ei.value)


# ------------------------------------------------------------- engine e2e
@pytest.fixture(scope="module")
def setup():
    import jax
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("stablelm-1.6b").smoke()
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


def test_engine_lifecycle_spans_and_metric_consistency(setup, tmp_path):
    from repro.serve.engine import ServeEngine

    cfg, params = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (4, 7, 4, 5)]
    max_new = 8
    obs = Observability()
    with ServeEngine(cfg, params, decode_chunk=4, obs=obs) as eng:
        t_run0 = time.perf_counter()
        reqs = [eng.submit(p, max_new) for p in prompts]
        outs = [eng.result(r, timeout=240.0) for r in reqs]
        wall = time.perf_counter() - t_run0
        assert all(o.shape == (max_new,) for o in outs)

        # ---- request timestamps: monotone lifecycle on one clock
        for r in reqs:
            assert r.submitted_at <= r.admitted_at <= r.first_token_at \
                <= r.finished_at
            assert r.ttft == pytest.approx(
                r.first_token_at - r.submitted_at)
            assert r.queue_wait >= 0.0

        # ---- spans reconstruct every lifecycle
        spans = obs.tracer.spans()
        by_name = {}
        for name, track, ts, te, args in spans:
            assert te >= ts
            by_name.setdefault(name, []).append((track, ts, te, args))
        for required in ("queued", "admitted", "decode", "retired",
                         "serve.admission", "serve.cycle"):
            assert required in by_name, f"missing {required} spans"
        # one queued->admitted chain and one retired instant per request
        for evt in ("queued", "admitted", "retired"):
            got = sorted(a["req"] for _, _, _, a in by_name[evt])
            assert got == sorted(r.id for r in reqs)
        assert all(t == TRACK_ENGINE
                   for t, _, _, _ in by_name["serve.cycle"])
        # "decode" spans are the request lifecycle on slot tracks; the
        # decode PIPE body is "pipe.decode" on line tracks
        slot_decode = [(t, ts, te) for t, ts, te, _ in by_name["decode"]]
        assert slot_decode
        assert all(t.startswith("slot") for t, _, _ in slot_decode)
        assert all(t.startswith("line")
                   for t, _, _, _ in by_name["pipe.decode"])

        # ---- acceptance: per-slot decode span time bounded by wall time
        per_slot = {}
        for track, ts, te in slot_decode:
            per_slot[track] = per_slot.get(track, 0.0) + (te - ts)
        assert per_slot and all(v <= wall for v in per_slot.values())

        # ---- metrics agree with the engine's own bookkeeping
        snap = obs.metrics.snapshot()
        assert snap["serve.ttft_s"]["count"] == len(reqs)  # acceptance
        assert snap["serve.queue_wait_s"]["count"] == len(reqs)
        assert snap["serve.requests.admitted"] == len(reqs)
        assert snap["serve.requests.retired"] == len(reqs)
        assert snap["serve.tokens_out"] == eng.stats["tokens_out"]
        assert snap["engine.cycle_s"]["count"] == \
            len(by_name["serve.cycle"])
        # drained: gauges back to idle
        assert snap["serve.queue_depth"] == 0
        assert snap["serve.resident_rows"] == 0
        assert snap["pool.blocks_used"] == 0
        # retired prompts' blocks stay PARKED in the prefix trie (rc 1)
        # when the cache is on; free + parked covers every usable block
        parked = eng._prefix.num_parked if eng._prefix is not None else 0
        assert snap["pool.blocks_free"] + parked == eng._pool.num_blocks - 1
        # TTFT histogram and per-request properties tell one story
        assert snap["serve.ttft_s"]["max"] <= wall

        path = str(tmp_path / "trace.json")
        obs.export(path)
    payload = json.loads(open(path).read())
    tracks = _validate_chrome_trace(payload)
    assert TRACK_ENGINE in tracks.values()
    assert any(t.startswith("slot") for t in tracks.values())
    assert any(t.startswith("line") for t in tracks.values())
    assert payload["otherData"]["metrics"]["serve.requests.retired"] \
        == len(reqs)


def test_preemption_reentry_visible_in_trace(setup):
    """Pool exhaustion preempts the youngest row; its track must show the
    re-entry: a second queued/admitted chain, a preempted instant, and
    preempt/grow counters equal to the engine's stats."""
    from repro.serve.engine import ServeEngine

    cfg, params = setup
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, size=16).astype(np.int32)
               for _ in range(2)]
    obs = Observability()
    with ServeEngine(cfg, params, decode_chunk=4, kv_blocks=10,
                     block_size=4, paged_impl="gather", obs=obs) as eng:
        reqs = [eng.submit(p, max_new=16) for p in prompts]
        [r.result(timeout=240.0) for r in reqs]
        stats = dict(eng.stats)
        snap = obs.metrics.snapshot()
        spans = obs.tracer.spans()
    assert stats["preempted"] >= 1
    assert snap["serve.requests.preempted"] == stats["preempted"]
    assert snap["pool.grown_blocks"] == stats["grown_blocks"]
    pre = [(t, a) for n, t, _, _, a in spans if n == "preempted"]
    assert len(pre) == stats["preempted"]
    victim_ids = {a["req"] for _, a in pre}
    # the victim was admitted more than once: the re-entry is on the trace
    for vid in victim_ids:
        admits = [1 for n, _, _, _, a in spans
                  if n == "admitted" and a["req"] == vid]
        assert len(admits) >= 2
        vr = next(r for r in reqs if r.id == vid)
        assert vr.preempted_count >= 1
    # TTFT still counts each request ONCE (first token only)
    assert snap["serve.ttft_s"]["count"] == len(reqs)
