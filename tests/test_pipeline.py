"""In-graph control-flow tests for the Pipeflow pipeline subsystem.

Everything here runs on executor condition/multi-condition tasks only —
deterministic seeds, no sleeps (event handshakes with generous timeouts
prove concurrency without timing assumptions).
"""
import threading
from collections import defaultdict

import pytest

from repro.core import ACCEL, Executor, Taskflow, TaskError
from repro.pipeline import (DataPipe, DataPipeline, Pipe, Pipeflow, Pipeline,
                            PipeType)


def _counted_stop(n):
    """First-pipe body admitting exactly n tokens."""
    def admit(pf):
        if pf.token >= n:
            pf.stop()
    return admit


# --------------------------------------------------------------- token order
def test_tokens_visit_stages_in_order(executor):
    N, L, S = 23, 3, 4
    lock = threading.Lock()
    log = []

    def mk(s):
        def stage(pf):
            if s == 0 and pf.token >= N:
                pf.stop()
                return
            with lock:
                log.append((s, pf.token, pf.line))
        return stage

    kinds = [PipeType.SERIAL, PipeType.PARALLEL, PipeType.SERIAL,
             PipeType.PARALLEL]
    pl = Pipeline(L, *[Pipe(kinds[s], mk(s)) for s in range(S)])
    pl.run(executor).wait(30)
    assert pl.num_tokens == N

    per_stage = defaultdict(list)
    per_token = defaultdict(list)
    for s, tok, line in log:
        per_stage[s].append(tok)
        per_token[tok].append(s)
        assert line == tok % L  # token t runs on line t % L
    # SERIAL stages see tokens in strict submission order
    assert per_stage[0] == list(range(N))
    assert per_stage[2] == list(range(N))
    # PARALLEL stages see every token exactly once (any order)
    assert sorted(per_stage[1]) == list(range(N))
    assert sorted(per_stage[3]) == list(range(N))
    # every token visits stages in pipeline order
    assert all(per_token[t] == [0, 1, 2, 3] for t in range(N))


def test_serial_stage_admits_one_line_at_a_time(executor):
    N, L = 17, 4
    lock = threading.Lock()
    active = defaultdict(int)
    peak = defaultdict(int)

    def mk(s):
        def stage(pf):
            if s == 0 and pf.token >= N:
                pf.stop()
                return
            with lock:
                active[s] += 1
                peak[s] = max(peak[s], active[s])
            with lock:
                active[s] -= 1
        return stage

    pl = Pipeline(L, Pipe(PipeType.SERIAL, mk(0)),
                  Pipe(PipeType.SERIAL, mk(1)),
                  Pipe(PipeType.SERIAL, mk(2)))
    pl.run(executor).wait(30)
    assert all(peak[s] == 1 for s in range(3)), peak


def test_parallel_stage_overlaps_lines(executor):
    """Two tokens must be able to occupy a PARALLEL stage simultaneously:
    each waits (bounded) for the other's arrival — deadlock-free only if the
    scheduler really overlaps the lines."""
    arrived = [threading.Event(), threading.Event()]
    ok = []

    def par(pf):
        if pf.token < 2:
            arrived[pf.token].set()
            ok.append(arrived[1 - pf.token].wait(timeout=30))

    pl = Pipeline(2, Pipe(PipeType.SERIAL, _counted_stop(4)),
                  Pipe(PipeType.PARALLEL, par))
    pl.run(executor).wait(30)
    assert ok.count(True) == 2


# ------------------------------------------------------------- stop protocol
def test_stop_mid_stream_drains_in_flight():
    """Observer-based exact accounting: N tokens × S stages + the stopping
    admit + the start condition — nothing more runs after stop()."""
    from repro.core import Observer

    class Count(Observer):
        def __init__(self):
            self.n = 0
            self.lock = threading.Lock()

        def on_entry(self, worker_id, domain, task):
            with self.lock:
                self.n += 1

    obs = Count()
    ex = Executor(domains={"host": 4}, observer=obs)
    N, L, S = 10, 3, 3
    done = defaultdict(int)
    lock = threading.Lock()

    def mk(s):
        def stage(pf):
            if s == 0 and pf.token >= N:
                pf.stop()
                return
            with lock:
                done[pf.token] += 1
        return stage

    pl = Pipeline(L, *[Pipe(PipeType.SERIAL if s != 1 else PipeType.PARALLEL,
                            mk(s)) for s in range(S)])
    pl.run(ex).wait(30)
    ex.shutdown(wait=True)
    assert pl.num_tokens == N
    # every admitted token drained through ALL stages
    assert dict(done) == {t: S for t in range(N)}
    assert obs.n == N * S + 2  # + stopping admit + start condition


def test_stop_outside_first_pipe_raises(executor):
    def bad(pf):
        pf.stop()

    pl = Pipeline(2, Pipe(PipeType.SERIAL, _counted_stop(3)),
                  Pipe(PipeType.SERIAL, bad))
    with pytest.raises(TaskError, match="first pipe"):
        pl.run(executor).wait(30)


# ----------------------------------------------------- zero dedicated threads
def test_pipeline_runs_on_executor_workers_only(executor):
    before = set(threading.enumerate())
    names = set()
    lock = threading.Lock()

    def rec(pf):
        if pf.token >= 12:
            pf.stop()
            return
        with lock:
            names.add(threading.current_thread().name)

    pl = Pipeline(3, Pipe(PipeType.SERIAL, rec),
                  Pipe(PipeType.PARALLEL, lambda pf: names.add(
                      threading.current_thread().name)))
    pl.run(executor).wait(30)
    after = set(threading.enumerate())
    assert names and all(n.startswith("repro-worker-") for n in names)
    assert after - before == set()  # the pipeline spawned ZERO threads


def test_pipe_domain_routes_to_accel_workers():
    ex = Executor(domains={"host": 2, "accel": 1})
    names = defaultdict(set)
    lock = threading.Lock()

    def mk(s):
        def stage(pf):
            if s == 0 and pf.token >= 6:
                pf.stop()
                return
            with lock:
                names[s].add(threading.current_thread().name)
        return stage

    pl = Pipeline(2, Pipe(PipeType.SERIAL, mk(0)),
                  Pipe(PipeType.SERIAL, mk(1), domain=ACCEL))
    pl.run(ex).wait(30)
    ex.shutdown(wait=True)
    assert all("accel" in n for n in names[1]) and names[1]
    assert all("host" in n for n in names[0]) and names[0]


# ------------------------------------------------------------- graph statics
def test_static_cyclic_graph_shape():
    pl = Pipeline(3, Pipe(PipeType.SERIAL, lambda pf: pf.stop()),
                  Pipe(PipeType.PARALLEL, lambda pf: None))
    # L*S multi-condition slots + 1 start condition, built ONCE
    assert pl.taskflow.num_tasks() == 3 * 2 + 1
    dump = pl.taskflow.dump()
    assert "style=dashed" in dump  # every pipeline edge is weak (§3.4)
    assert dump.count("diamond") == 3 * 2 + 1  # all condition-family tasks


def test_pipeline_validation():
    with pytest.raises(ValueError, match="at least one line"):
        Pipeline(0, Pipe(PipeType.SERIAL, lambda pf: None))
    with pytest.raises(ValueError, match="at least one pipe"):
        Pipeline(1)
    with pytest.raises(ValueError, match="first pipe must be SERIAL"):
        Pipeline(1, Pipe(PipeType.PARALLEL, lambda pf: None))


# ------------------------------------------------------------------- re-runs
def test_rerun_continues_token_stream(executor):
    seen = []
    budget = [5]

    def admit(pf):
        if pf.token >= budget[0]:
            pf.stop()
            return
        seen.append(pf.token)

    pl = Pipeline(2, Pipe(PipeType.SERIAL, admit))
    pl.run(executor).wait(30)
    assert seen == list(range(5))
    budget[0] = 12  # restart pattern: drained pipeline re-armed by run()
    pl.run(executor).wait(30)
    assert seen == list(range(12))
    assert pl.num_tokens == 12


def test_reset_while_running_raises(executor):
    gate = threading.Event()

    def admit(pf):
        if pf.token >= 1:
            pf.stop()
            return
        gate.wait(30)

    pl = Pipeline(1, Pipe(PipeType.SERIAL, admit))
    topo = pl.run(executor)
    with pytest.raises(RuntimeError, match="running pipeline"):
        pl.reset()
    gate.set()
    topo.wait(30)
    pl.reset()  # fine once drained


def test_executor_rejects_concurrent_resubmission(executor):
    gate = threading.Event()
    tf = Taskflow("twice")
    tf.static(lambda: gate.wait(30))
    topo = executor.run(tf)
    with pytest.raises(RuntimeError, match="already running"):
        executor.run(tf)
    gate.set()
    topo.wait(30)
    executor.run(tf).wait(30)  # sequential re-run stays legal


# --------------------------------------------------- token-level deferral
def test_defer_parks_until_dependency_completes(executor):
    """Token 2 defers on token 0 while 0 is still mid-pipeline: admission
    pauses (no spinning, no overtaking at the admission point), in-flight
    tokens drain, and the resume re-runs the SAME token number exactly once
    after 0 completes the last pipe."""
    hold0 = threading.Event()
    released_before_resume = []
    admits = []
    deferred = [False]
    lock = threading.Lock()

    def admit(pf):
        if pf.token >= 5:
            pf.stop()
            return
        if pf.token == 2 and not deferred[0]:
            deferred[0] = True
            released_before_resume.append(hold0.is_set())
            pf.defer(0)
            return
        with lock:
            admits.append(pf.token)

    def mid(pf):
        if pf.token == 0:
            hold0.wait(30)

    pl = Pipeline(3, Pipe(PipeType.SERIAL, admit),
                  Pipe(PipeType.PARALLEL, mid),
                  Pipe(PipeType.SERIAL, lambda pf: None))
    topo = pl.run(executor)
    # token 2 is parked on token 0, which is blocked in stage 1 -> the
    # pipeline cannot finish until we release it
    assert not topo.event.wait(0.2)
    hold0.set()
    topo.wait(30)
    assert admits == [0, 1, 2, 3, 4]      # same token resumed, order kept
    assert released_before_resume == [False]  # it really parked first
    assert pl.num_token_deferrals == 1
    assert pl.num_resumes == 1            # resume accounting: exactly once


def test_defer_on_completed_token_reruns_immediately(executor):
    seen = []
    d = [False]

    def admit(pf):
        if pf.token >= 4:
            pf.stop()
            return
        if pf.token == 3 and not d[0]:
            d[0] = True
            pf.defer(0)                   # token 0 completed long ago
            return
        seen.append(pf.token)

    pl = Pipeline(2, Pipe(PipeType.SERIAL, admit))
    pl.run(executor).wait(30)
    assert seen == [0, 1, 2, 3]
    assert pl.num_token_deferrals == 1 and pl.num_resumes == 1


def test_defer_validation(executor):
    with pytest.raises(TaskError, match="first pipe"):
        pl = Pipeline(2, Pipe(PipeType.SERIAL, _counted_stop(2)),
                      Pipe(PipeType.SERIAL, lambda pf: pf.defer(0)))
        pl.run(executor).wait(30)
    with pytest.raises(TaskError, match="itself"):
        pl = Pipeline(2, Pipe(PipeType.SERIAL,
                              lambda pf: pf.defer(pf.token)))
        pl.run(executor).wait(30)
    with pytest.raises(TaskError, match="un-minted"):
        pl = Pipeline(2, Pipe(PipeType.SERIAL, lambda pf: pf.defer(7)))
        pl.run(executor).wait(30)


def test_defer_resumes_across_reruns(executor):
    """The monotone token stream + completion watermark survive the re-arm
    path: a second run() can defer on tokens completed in the FIRST run."""
    log = []
    budget = [3]
    d = [False]

    def admit(pf):
        if pf.token >= budget[0]:
            pf.stop()
            return
        if pf.token == 4 and not d[0]:
            d[0] = True
            pf.defer(1)                   # completed in run 1
            return
        log.append(pf.token)

    pl = Pipeline(2, Pipe(PipeType.SERIAL, admit))
    pl.run(executor).wait(30)
    budget[0] = 6
    pl.run(executor).wait(30)
    assert log == [0, 1, 2, 3, 4, 5]
    assert pl.num_token_deferrals == 1 and pl.num_resumes == 1


# ------------------------------------------------------------ stage_times
def test_stage_times_accumulate_monotone(executor):
    """stage_times sums body wall time per pipe name, over lines AND runs:
    a second run only ever grows the numbers."""
    import time as _time
    budget = [6]

    def admit(pf):
        if pf.token >= budget[0]:
            pf.stop()
            return
        _time.sleep(0.002)

    def work(pf):
        _time.sleep(0.002)

    pl = Pipeline(2, Pipe(PipeType.SERIAL, admit, name="admit"),
                  Pipe(PipeType.PARALLEL, work, name="work"))
    assert pl.stage_times == {"admit": 0.0, "work": 0.0}
    pl.run(executor).wait(30)
    first = pl.stage_times
    # every body slept >= 2ms per visit: 6 admit visits + 6 work visits
    # (the stopping admit adds a 7th, sleepless, visit)
    assert first["admit"] >= 6 * 0.002
    assert first["work"] >= 6 * 0.002
    budget[0] = 12
    pl.run(executor).wait(30)
    second = pl.stage_times
    assert set(second) == {"admit", "work"}
    assert all(second[k] >= first[k] for k in first)  # monotone across runs
    assert second["work"] >= 12 * 0.002


def test_stage_times_no_slot_races_under_parallel(executor):
    """Two lines INSIDE the PARALLEL stage at once (event rendezvous): the
    per-(line, pipe) counters must not lose either line's interval — the
    summed stage time covers both concurrent bodies, not just one."""
    import time as _time
    arrived = [threading.Event(), threading.Event()]
    ok = []

    def par(pf):
        if pf.token < 2:
            arrived[pf.token].set()
            ok.append(arrived[1 - pf.token].wait(timeout=30))
            _time.sleep(0.01)

    pl = Pipeline(2, Pipe(PipeType.SERIAL, _counted_stop(4), name="admit"),
                  Pipe(PipeType.PARALLEL, par, name="par"))
    pl.run(executor).wait(30)
    assert ok.count(True) == 2  # both tokens really overlapped in the stage
    # both overlapped bodies slept 10ms: a lost per-slot update would leave
    # the sum below 20ms
    assert pl.stage_times["par"] >= 2 * 0.01


def test_stage_times_fresh_on_rebuild(executor):
    """A rebuilt Pipeline (the serve engine rebuilds its resident pipeline
    on geometry change) starts from zero, while reset()+rerun of the SAME
    object keeps accumulating (documented: summed over runs)."""
    def mk():
        return Pipeline(2, Pipe(PipeType.SERIAL, _counted_stop(5),
                                name="admit"))

    pl = mk()
    pl.run(executor).wait(30)
    assert pl.stage_times["admit"] > 0.0
    rebuilt = mk()
    assert rebuilt.stage_times == {"admit": 0.0}


def test_stage_times_promote_to_tracer_spans(executor):
    """With a repro.obs.Tracer attached, every pipe-body interval is also a
    span on that line's track, consistent with the stage_times aggregate."""
    from repro.obs import Tracer

    tr = Tracer()
    pl = Pipeline(2, Pipe(PipeType.SERIAL, _counted_stop(4), name="admit"),
                  Pipe(PipeType.PARALLEL, lambda pf: None, name="work"))
    pl.tracer = tr
    pl.run(executor).wait(30)
    spans = tr.spans()
    # 4 tokens x 2 stages + the stopping admit visit
    assert len(spans) == 4 * 2 + 1
    assert {s[1] for s in spans} == {"line0", "line1"}
    assert {s[0] for s in spans} == {"pipe.admit", "pipe.work"}
    assert all(s[3] >= s[2] for s in spans)
    # span sum == stage_times aggregate (same measurements, two views)
    agg = sum(s[3] - s[2] for s in spans)
    st = pl.stage_times
    assert abs(agg - (st["admit"] + st["work"])) < 1e-6
    # detaching stops recording without disturbing accumulation
    pl.tracer = None
    pl.run(executor).wait(30)
    assert len(tr.spans()) == 4 * 2 + 1


# -------------------------------------------------------------- data passing
def test_data_pipeline_threads_buffers(executor):
    outs = []

    def produce(pf):
        if pf.token >= 9:
            pf.stop()
            return None
        return pf.token

    dp = DataPipeline(3,
                      DataPipe(PipeType.SERIAL, produce),
                      DataPipe(PipeType.PARALLEL, lambda pf, x: x * x + pf.line),
                      DataPipe(PipeType.SERIAL, lambda pf, x: outs.append(x)))
    dp.run(executor).wait(30)
    assert outs == [t * t + (t % 3) for t in range(9)]


def test_prefetcher_get_before_start_self_arms(executor):
    """get() on a never-started executor-mode prefetcher must arm the
    pipeline itself instead of blocking until timeout."""
    from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM

    cfg = DataConfig(vocab_size=32, seq_len=4, global_batch=1, seed=2)
    p = Prefetcher(SyntheticLM(cfg).batch_at, depth=2, executor=executor)
    step, _ = p.get(timeout=30)  # no start(): get() pumps before blocking
    assert step == 0
    p.stop()


def test_prefetcher_is_a_pipeline_client(executor):
    from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM

    cfg = DataConfig(vocab_size=64, seq_len=8, global_batch=2, seed=1)
    src = SyntheticLM(cfg)
    p = Prefetcher(src.batch_at, depth=3, executor=executor)
    assert p.start()
    steps = [p.get(timeout=30)[0] for _ in range(9)]
    assert steps == list(range(9))  # PARALLEL staging, still in step order
    p.stop()
    # determinism vs the manual drive
    q = Prefetcher(SyntheticLM(cfg).batch_at, depth=3)
    assert q.produce_one()
    import numpy as np
    s0, b0 = q.get(timeout=30)
    assert s0 == 0
    np.testing.assert_array_equal(b0["tokens"],
                                  SyntheticLM(cfg).batch_at(0)["tokens"])
