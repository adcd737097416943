"""One run of one benchmark cell: set up, measure a window, check, report.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file it names, ``traffic/<traffic>.json``, and one reader
``metrics/<metric>.py`` per per-layer metric. Adding a cell adds files
and entries; nothing here changes.

The system under test is ``repro.serve.engine.ServeEngine``, built with
the configuration file's ``engine`` options only (the options a
deployment's operator sets), and, where the file carries a ``mesh``
(``{"data": 1, "model": 4}``), on that tensor-parallel mesh over the
cell's own chips. Every other choice — kernels, chunk sizes, overlap,
prefix caching — is the program's own default, so a PR that changes a
default is measured.

A run:

1. enables the persistent compile cache inside the checkout, and fails
   with no result when JAX finds no TPU, too few chips, or a chip kind
   missing from ``peaks.json``;
2. makes bf16 weights on the device from ``--seed`` in one jitted call,
   each leaf born on its shard where the file names a mesh;
3. builds one engine and warms every prompt-window shape the traffic can
   use, by sending one request at each power-of-two boundary of the
   mix's prompt range, each admitted alone;
4. starts the traffic: a closed loop opens the window once every client
   has had a request returned, an open loop after its lead-in;
5. measures ``--seconds`` seconds (with ``--trace 1`` the last
   ``trace_s`` of them under the profiler, where the configuration file
   gives ``trace_s``, else all of them), then stops sending, waits for
   every request still out, reads the device's peak memory and frees
   the engine;
6. checks what the window served against the float32 reference
   (``check.py``) and prints one JSON line.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

import loadgen
import opcount

__all__ = ["NoChip", "CellError", "load_cell", "run"]

#: compile events counted inside the window: a jit cache miss traces, and
#: a trace is followed by a persistent-cache lookup or a compile
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
#: a request still out this long after the window closed never comes
DRAIN_S = 150.0
#: requests queued per closed-loop client (far more than a window uses)
PER_CLIENT = 64
#: seconds the profiler starts ahead of the window
TRACE_LEAD_S = 2.0


class NoChip(Exception):
    """No accelerator, too few chips, or a chip kind with no peaks."""


class CellError(Exception):
    """The cell's files are missing or inconsistent."""


# ------------------------------------------------------------------ files
def _read_json(path: Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise CellError(f"missing {path}") from None


def load_cell(root: Path, workload: str) -> Dict[str, Any]:
    """The manifest entry of ``workload`` with its configuration, traffic
    mix and metric lists resolved from their files."""
    root = Path(root)
    man = _read_json(root / "BENCHMARK.json")
    bench = root / man["paths"][0]
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    confs = {c["name"]: c for c in man["configs"]}
    conf = _read_json(root / confs[w["config"]]["file"])
    if not conf.get("trace_s", 1) > 0:
        raise CellError(f"trace_s {conf['trace_s']!r}: give a length > 0")
    mix = _read_json(bench / "traffic" / f"{w['traffic']}.json")

    # every end-to-end metric is every cell's; a per-layer metric lists
    # the cells it is read in
    layer = [m for m in man["per_layer"] if workload in m["workloads"]]
    for m in layer:
        if not (bench / "metrics" / f"{m['name']}.py").is_file():
            raise CellError(f"no reader metrics/{m['name']}.py")
    return {"name": workload, "entry": w, "config": conf, "mix": mix,
            "end_to_end": man["end_to_end"], "per_layer": layer, "bench": bench,
            "root": root,
            "peaks": _read_json(bench / "peaks.json")["devices"]}


def read_metric(bench: Path, name: str, rec: Dict) -> Optional[float]:
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


# ----------------------------------------------------------------- device
def check_device(peaks: Dict, chips: int, need_chip: bool):
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if need_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
        if kind not in peaks:
            raise NoChip(f"device kind {kind!r} is not in peaks.json")
        peak = peaks[kind]
    else:
        # CPU tests only: a device metric from here is never reported
        peak = next(iter(peaks.values()))
    return devs[:chips], peak


# ------------------------------------------------------------------ model
def build_config(conf: Dict):
    """The program's ModelConfig for this configuration file, checked by
    the file's family module against what the file states."""
    from repro.configs import get_config
    prog = conf["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["fields"])
    bad = opcount.family(conf).program_departures(conf, cfg)
    if bad:
        raise CellError(f"{prog['arch']}: the program's config departs from "
                        f"the configuration file: {bad}")
    return cfg


def mesh_ctx(conf: Dict, devs: List):
    """The serve ``ShardCtx`` over the cell's own devices that the file's
    ``mesh`` names (``{"data": d, "model": m}``), or None without one."""
    mesh = conf.get("mesh")
    if mesh is None:
        return None
    if set(mesh) != {"data", "model"}:
        raise CellError(f"mesh {mesh}: give its data and model axes")
    shape = (int(mesh["data"]), int(mesh["model"]))
    if shape[0] * shape[1] != len(devs):
        raise CellError(f"mesh {mesh} spans {shape[0] * shape[1]} devices; "
                        f"the cell has {len(devs)} chips")
    import jax
    from repro.launch.mesh import make_ctx
    auto = (jax.sharding.AxisType.Auto,) * 2
    return make_ctx(jax.make_mesh(shape, ("data", "model"), axis_types=auto,
                                  devices=devs))


def make_weights(cfg, seed: int, ctx=None):
    """The program's weights from ``seed``; with a mesh ``ctx``, each leaf
    is made on its shard as the engine places it, so no device ever holds
    the whole model."""
    import jax
    from repro.models import lm
    init = functools.partial(lm.init_params, cfg)
    key = jax.random.PRNGKey(seed)
    if ctx is None:
        params = jax.jit(init)(key)
    else:
        from repro.distributed.sharding import serve_param_shardings
        shard = serve_param_shardings(cfg, jax.eval_shape(init, key), ctx)
        params = jax.jit(init, out_shardings=shard)(key)
    return jax.block_until_ready(params)


def warm_lengths(lo: int, hi: int) -> List[int]:
    """Prompt lengths at both sides of every power of two in the range,
    and its ends: each lands in a different power-of-two window shape."""
    out = {lo, hi}
    p = 1
    while p < hi:
        for x in (p, p + 1):
            if lo <= x <= hi:
                out.add(x)
        p *= 2
    return sorted(out)


def warm_up(eng, mix: Dict, vocab: int, seed: int) -> int:
    """One request per warm length, each admitted alone (the next is sent
    once the previous has a slot), so every window-0 width compiles; 17
    new tokens cross a page boundary, so table growth compiles too."""
    rng = np.random.default_rng([seed, 1])
    reqs = []
    for n in warm_lengths(int(mix["prompt"]["lo"]), int(mix["prompt"]["hi"])):
        r = eng.submit(loadgen.prompt_tokens(rng, n, vocab), max_new=17)
        reqs.append(r)
        while r.admitted_at is None and not r.done():
            time.sleep(0.002)
    for r in reqs:
        eng.result(r, timeout=900.0)
    return len(reqs)


def _wait_until(t: float) -> None:
    time.sleep(max(0.0, t - time.perf_counter()))


# ---------------------------------------------------------------- traffic
class Record:
    """One request as the client saw it."""
    __slots__ = ("due", "sent", "req", "prompt", "max_new", "in_window",
                 "out", "error")

    def __init__(self, due, prompt, max_new, in_window=False):
        self.due = due
        self.sent = None
        self.req = None
        self.prompt = prompt
        self.max_new = max_new
        self.in_window = in_window
        self.out = None
        self.error = None


def _finish(eng, rec: Record, timeout: float) -> None:
    try:
        rec.out = np.asarray(eng.result(rec.req, timeout=timeout))
    except Exception as e:          # a request that never comes
        rec.error = f"{type(e).__name__}: {e}"


class ClosedLoop:
    """``clients`` threads, each sending its next request when its last
    returns. Opens the window once every client has had one returned."""

    def __init__(self, eng, mix, seed, vocab):
        self.eng = eng
        self.queues = loadgen.closed_loop_requests(mix, seed, vocab,
                                                   PER_CLIENT)
        self.records: List[Record] = []
        self.lock = threading.Lock()
        self.stop = threading.Event()
        self.returned = [threading.Event() for _ in self.queues]
        self.threads = [threading.Thread(target=self._client, args=(i,),
                                         daemon=True)
                        for i in range(len(self.queues))]

    def _client(self, i):
        for prompt, max_new in self.queues[i]:
            if self.stop.is_set():
                return
            rec = Record(time.perf_counter(), prompt, max_new)
            rec.req = self.eng.submit(prompt, max_new=max_new)
            rec.sent = rec.due
            with self.lock:
                self.records.append(rec)
            _finish(self.eng, rec, DRAIN_S + 600.0)
            self.returned[i].set()

    def start(self):
        """Start the clients; returns when the window may open."""
        for t in self.threads:
            t.start()
        for e in self.returned:
            if not e.wait(900.0):
                raise CellError("a client had no request returned in 900 s")
        return time.perf_counter()

    def close(self):
        self.stop.set()
        for t in self.threads:
            t.join(DRAIN_S)
        return any(t.is_alive() for t in self.threads)


class OpenLoop:
    """One thread sending each request at its due time."""

    def __init__(self, eng, mix, seed, vocab, seconds):
        self.eng = eng
        self.lead = float(mix.get("lead_s", 0.0))
        sched = loadgen.open_loop_schedule(mix, seed, vocab, seconds)
        self.records = [Record(t, p, m, w) for t, p, m, w in sched]
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._send, daemon=True)

    def _send(self):
        for rec in self.records:
            while True:
                now = time.perf_counter()
                if self.stop.is_set() or now >= rec.due:
                    break
                time.sleep(min(rec.due - now, 0.05))
            if self.stop.is_set():
                return
            rec.sent = time.perf_counter()
            rec.req = self.eng.submit(rec.prompt, max_new=rec.max_new)

    def start(self):
        """Start sending; returns the time the window opens."""
        t0 = time.perf_counter() + 0.05
        for rec in self.records:
            rec.due += t0
        self.thread.start()
        return t0 + self.lead

    def close(self):
        self.stop.set()
        self.thread.join(DRAIN_S)
        for rec in self.records:
            if rec.req is not None:
                _finish(self.eng, rec, DRAIN_S)
        return self.thread.is_alive()


# ---------------------------------------------------------------- metrics
def window_metrics(records: List[Record], t_open: float, t_close: float,
                   tok_open: int, tok_close: int) -> Dict[str, Any]:
    """End-to-end numbers over the window ``[t_open, t_close)``, from the
    clients' due times and the requests' own timestamps."""
    win = t_close - t_open
    due = [r for r in records if t_open <= r.due < t_close]
    ttft = [(r.req.first_token_at - r.due)
            if r.req is not None and r.req.first_token_at is not None
            and r.error is None else math.inf for r in due]
    qwait = [(r.req.admitted_at - r.due)
             if r.req is not None and r.req.admitted_at is not None
             and r.error is None else math.inf for r in due]
    tpot = []
    firsts = 0
    for r in records:
        q = r.req
        if q is None:
            continue
        if q.first_token_at is not None and t_open <= q.first_token_at \
                < t_close:
            firsts += 1
        if (r.out is not None and len(r.out) > 1 and q.finished_at is not None
                and t_open <= q.finished_at < t_close):
            tpot.append((q.finished_at - q.first_token_at)
                        / (len(r.out) - 1))
    mid = t_open + win / 2

    def median(v):
        return float(np.median(v)) if v else math.nan
    return {
        "ttft_p90_s": loadgen.nearest_rank(ttft, 0.9),
        "tpot_p90_ms": loadgen.nearest_rank(tpot, 0.9) * 1e3,
        "window_s": win,
        "qwait_first_s": median([w for r, w in zip(due, qwait)
                                 if r.due < mid]),
        "qwait_second_s": median([w for r, w in zip(due, qwait)
                                  if r.due >= mid]),
        "waiting_at_close": sum(
            1 for r in due if r.req is None or r.req.admitted_at is None
            or r.req.admitted_at >= t_close),
        "tokens": tok_close - tok_open + firsts,
        "tokens_per_s": (tok_close - tok_open + firsts) / win,
        "queue_wait_p90_s": loadgen.nearest_rank(qwait, 0.9),
        "n_due": len(due), "n_tpot": len(tpot),
        "failed": sum(1 for r in due if r.error is not None or r.out is None),
        "late_p100_s": max([r.sent - r.due for r in due
                            if r.sent is not None] or [0.0]),
    }


def request_rows(records: List[Record], t0: float) -> List[Dict]:
    """Per-request timestamps (seconds from ``t0``) for the readers."""
    rows = []
    for r in records:
        q = r.req
        if q is None:
            continue

        def rel(t):
            return None if t is None else t - t0
        rows.append({"due": rel(r.due), "admitted": rel(q.admitted_at),
                     "first": rel(q.first_token_at),
                     "finished": rel(q.finished_at),
                     "prompt": int(len(r.prompt)),
                     "out": None if r.out is None else int(len(r.out))})
    return rows


# -------------------------------------------------------------------- run
def run(root: Path, workload: str, seed: int, seconds: int, trace: bool,
        need_chip: bool = True, t_start: Optional[float] = None,
        control: bool = False, save_records: Optional[str] = None,
        log=print) -> Dict[str, Any]:
    """One run; returns the result object (its last line)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(root, workload)
    import jax
    # arrays that predate the run are not its to free
    before = jax.live_arrays()
    from repro.launch.compile_cache import enable_compile_cache
    if need_chip:
        log(f"compile cache: {enable_compile_cache()}")
    devs, peak = check_device(cell["peaks"], int(cell["entry"]["chips"]),
                              need_chip)
    from repro.serve.engine import ServeEngine
    import check
    import tracereduce

    compiles: Dict[str, int] = {}
    counting = threading.Event()

    def on_event(name, secs, **kw):
        if counting.is_set() and name in COMPILE_EVENTS:
            compiles[name] = compiles.get(name, 0) + 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    conf, mix = cell["config"], cell["mix"]
    cfg = build_config(conf)
    ctx = mesh_ctx(conf, devs)
    params = make_weights(cfg, seed, ctx)
    eng = ServeEngine(cfg, params, ctx=ctx, **conf["engine"])
    log(f"{cell['name']}: {cfg.name} {cfg.num_layers} layers d{cfg.d_model}"
        f" on {devs[0].device_kind} x {len(devs)}, mesh "
        f"{conf.get('mesh', 'none')}; engine "
        f"paged_impl={eng.paged_impl} decode_chunk={eng.decode_chunk} "
        f"prefill_chunk={eng.prefill_chunk}")
    n_warm = warm_up(eng, mix, cfg.vocab_size, seed)
    loop = ClosedLoop(eng, mix, seed, cfg.vocab_size) \
        if mix["loop"] == "closed" else \
        OpenLoop(eng, mix, seed, cfg.vocab_size, seconds)
    tok_start = eng.stats["tokens_out"]
    t_open = loop.start()

    # ---- the window
    # a --trace 1 run traces the window's last ``trace_s`` seconds (the
    # configuration's, or the whole window), the profiler started just
    # ahead: a trace's size, and the time to write and read it, grow with
    # its length, chips and layers
    t_trace = t_open + seconds - min(seconds, conf.get("trace_s", seconds))
    early = t_trace - TRACE_LEAD_S <= t_open
    prof = None
    if trace:
        prof = tracereduce.Profile(cell["bench"] / ".traces" / cell["name"])
        if early:
            _wait_until(t_trace - TRACE_LEAD_S)
            prof.start()
    _wait_until(t_open)
    counting.set()
    t_open = max(t_open, time.perf_counter())
    tok_open = eng.stats["tokens_out"]
    setup_s = t_open - t_start
    if trace:
        if not early:
            _wait_until(t_trace - TRACE_LEAD_S)
            prof.start()
        _wait_until(t_trace)
        prof.open_window()
        t_trace = max(t_trace, time.perf_counter())
        tok_trace = eng.stats["tokens_out"]
    _wait_until(t_open + seconds)
    tok_close = eng.stats["tokens_out"]
    t_close = time.perf_counter()
    if prof is not None:
        prof.stop()
        log(f"traced the window's last {t_close - t_trace:.3f} s; trace "
            f"written in {time.perf_counter() - t_close:.3f} s")
    counting.clear()
    n_compiles = sum(compiles.values())

    # ---- drain, then free the program's state
    stuck = loop.close()
    stats = dict(eng.stats)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    peak_bytes = max(peaks)
    log(f"peak bytes in use per chip: {peaks}")
    wm = window_metrics(loop.records, t_open, t_close, tok_open, tok_close)
    eng.close(timeout=30.0)
    decode_chunk = eng.decode_chunk
    loop.eng = None
    del eng, params
    gc.collect()
    kept = {id(a) for a in before}
    left = [a for a in jax.live_arrays() if id(a) not in kept]
    log(f"program state freed; {len(left)} arrays, "
        f"{sum(a.nbytes for a in left)} bytes left on the device, deleted")
    for a in left:
        a.delete()
    del left, before

    log(f"setup {setup_s:.3f} s ({n_warm} warm-up requests); window "
        f"{wm['window_s']:.3f} s; requests due in window {wm['n_due']}, "
        f"finished in window {wm['n_tpot']}, failed {wm['failed']}; "
        f"sender late by at most {wm['late_p100_s'] * 1e3:.1f} ms")
    log(f"window: {wm['tokens']} tokens, {wm['tokens_per_s']:.3f} tokens/s"
        f"{' (traced)' if trace else ''}; queue wait median of the first "
        f"half's requests {wm['qwait_first_s']:.3f} s, of the second "
        f"half's {wm['qwait_second_s']:.3f} s; {wm['waiting_at_close']} "
        f"due in the window not admitted at its close")
    log(f"tails: ttft_p90_s {wm['ttft_p90_s']:.6g} over {wm['n_due']} "
        f"requests, tpot_p90_ms {wm['tpot_p90_ms']:.6g} over "
        f"{wm['n_tpot']}")
    log(f"compiles in window: {n_compiles} {compiles}")
    log(f"engine stats: {stats}")

    # ---- correctness
    t_check = time.perf_counter()
    res = check.check_run(conf, seed, loop.records, stats,
                          tok_start=tok_start, stuck=stuck, control=control)
    log(f"check took {time.perf_counter() - t_check:.3f} s; drain "
        f"{t_check - t_close:.3f} s after the window")
    for line in res["lines"]:
        log(line)

    # ---- result line
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
    out: Dict[str, Any] = {"correct": res["correct"],
                           "attempted": wm["n_due"],
                           "failed": wm["failed"], "metrics": {},
                           "device": dev}
    if not trace:
        vals = dict(wm, setup_s=setup_s)
        for m in cell["end_to_end"]:
            v = vals[m["name"]]
            out["metrics"][m["name"]] = {
                "value": v if math.isfinite(v) else None, "unit": m["unit"]}
    else:
        t_read = time.perf_counter()
        rec = tracereduce.records_from_file(prof.path(), len(devs))
        log(f"trace read in {time.perf_counter() - t_read:.3f} s")
        rec.update({
            "requests": request_rows(loop.records, t_trace),
            "window_s": t_close - t_trace, "window_metrics": wm,
            "memory_peak_bytes": int(peak_bytes), "peak": peak,
            "decode_chunk": decode_chunk,
            "block_size": int(conf["engine"]["block_size"]),
            "model": conf,
        })
        rec["counters"] = {"tokens_out": tok_close - tok_trace}
        if save_records:
            tracereduce.save_records(rec, save_records)
            shutil.copy(prof.path(), save_records + ".xplane.pb")
        for m in cell["per_layer"]:
            v = read_metric(cell["bench"], m["name"], rec)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        for note in rec.get("notes", []):
            log(note)
        dev["busy_s"] = rec["busy_s"]
        dev["window_s"] = rec["trace_window_s"]
        out["breakdown"] = tracereduce.breakdown(rec)
        prof.cleanup()
    out["checks"] = res["checks"]
    return out
