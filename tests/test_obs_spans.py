"""Engine, pipeline and executor spans on the profiler's clock.

Every span of :func:`repro.obs.tracing.span` enters a
``jax.profiler.TraceAnnotation``, so a ``jax.profiler`` session records
it on the host plane beside the device's operations — with no
``Observability`` attached. These tests serve a smoke-size engine under a
CPU profiler session, read the ``.xplane.pb`` back with ``ProfileData``
and check where each span lies; with an ``Observability`` attached the
ring buffer must hold the same names.
"""
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.obs import Observability, Tracer
from repro.obs.tracing import span
from repro.serve.engine import ServeEngine

#: spans the engine and the pipeline open in every served run (the growth
#: spans need the paged pool, the window spans a multi-window prompt)
ENGINE_SPANS = {"pipe.admit", "pipe.prefill", "pipe.decode", "pipe.complete",
                "serve.admission", "serve.prefill", "serve.merge",
                "serve.prefill_window", "serve.window_sync", "serve.growth",
                "serve.dispatch", "serve.sync", "serve.bookkeeping",
                "serve.cycle"}


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("stablelm-1.6b").smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _host_events(logdir):
    """``{thread: [(name, start_ns, end_ns), ...]}`` of the host plane,
    names cut at the first ``#`` (the profiler's metadata)."""
    path, = glob.glob(str(logdir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out[f"{line.name}#{i}"] = [
                (e.name.split("#", 1)[0], e.start_ns,
                 e.start_ns + e.duration_ns) for e in line.events]
    return out


def _serve_traced(cfg, params, logdir, async_decode, obs=None):
    """Serve a short prompt and a three-window prompt under a profiler
    session; returns the engine's outputs."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=4).astype(np.int32),
               rng.integers(1, cfg.vocab_size, size=20).astype(np.int32)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # annotations only: a small trace
    with ServeEngine(cfg, params, decode_chunk=2, block_size=4,
                     prefill_chunk=8, paged_impl="gather",
                     async_decode=async_decode, obs=obs) as eng:
        assert eng.obs is obs
        jax.profiler.start_trace(str(logdir), profiler_options=opts)
        try:
            outs = eng.generate(prompts, max_new=10)
        finally:
            jax.profiler.stop_trace()
        assert eng.stats["prefill_windows"] >= 2
    return outs


@pytest.mark.parametrize("async_decode", [False, True],
                         ids=["sync", "async"])
def test_spans_reach_the_profiler_nested_in_pipe_decode(
        setup, tmp_path, monkeypatch, async_decode):
    """No ``Observability``: every span of the table reaches the host
    plane, and each decode cycle's dispatch -> sync -> bookkeeping lie in
    that order inside one ``pipe.decode``, on its thread."""
    monkeypatch.delenv("REPRO_OBS", raising=False)
    cfg, params = setup
    _serve_traced(cfg, params, tmp_path, async_decode)
    threads = _host_events(tmp_path)
    names = {n for evs in threads.values() for n, _, _ in evs}
    assert ENGINE_SPANS <= names
    assert "executor.sleep" in names
    ordered = 0
    for evs in threads.values():
        for name, s, e in evs:
            if name != "pipe.decode":
                continue
            inner = sorted((s2, e2, n2) for n2, s2, e2 in evs
                           if s <= s2 and e2 <= e and n2 in (
                               "serve.cycle", "serve.dispatch", "serve.sync",
                               "serve.bookkeeping"))
            assert inner and inner[0][2] == "serve.cycle"
            phases = [n2 for _, _, n2 in inner[1:]]
            if phases == ["serve.dispatch", "serve.sync",
                          "serve.bookkeeping"]:
                (_, d1, _), (s2, e2, _), (s3, _, _) = inner[1:]
                assert d1 <= s2 and e2 <= s3
                ordered += 1
    assert ordered >= 1


def test_ring_buffer_holds_the_profiler_names(setup, tmp_path):
    """With an ``Observability`` attached both sinks see one set of
    engine and pipeline span names, from the same calls."""
    cfg, params = setup
    obs = Observability()
    _serve_traced(cfg, params, tmp_path, async_decode=False, obs=obs)
    ring = {s[0] for s in obs.tracer.spans()
            if s[0].startswith(("serve.", "pipe."))}
    threads = _host_events(tmp_path)
    prof = {n for evs in threads.values() for n, _, _ in evs
            if n.startswith(("serve.", "pipe."))}
    assert ring == prof
    assert ENGINE_SPANS <= ring
    tracks = {s[1] for s in obs.tracer.spans() if s[0] == "pipe.decode"}
    assert tracks and all(t.startswith("line") for t in tracks)


def test_span_args_and_times_without_a_profiler():
    """Outside a profiler session a span still times its block and
    records into a tracer, args added inside the block included; a
    disabled tracer records nothing."""
    tr = Tracer()
    with span("serve.sync", tr, track="engine", req=[1, 2]) as sp:
        sp.annotate(emitted=3)
    (name, track, t0, t1, args), = tr.spans()
    assert (name, track) == ("serve.sync", "engine")
    assert args == {"req": [1, 2], "emitted": 3}
    assert (t0, t1) == (sp.t0, sp.t1) and sp.seconds >= 0.0
    tr.enabled = False
    with span("serve.sync", tr):
        pass
    with span("executor.sleep", domain="accel"):   # profiler sink only
        pass
    assert len(tr) == 1
