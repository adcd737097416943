"""The readers of the chip's idle time split by the program's spans
(``idlesplit.py``: ``idle_sync_frac``, ``idle_host_frac``,
``idle_handoff_frac``), against a hand-made window where each part is
known exactly, against the committed chip trace that predates the spans,
and against traced windows of both cells recorded on one TPU v5e with
the spans in place."""
import random

import pytest

import cellrun
import idlesplit
import tracereduce
from benchkit import HERE

READERS = ("idle_sync_frac", "idle_host_frac", "idle_handoff_frac")
#: 3-second traced windows (``run_cell.py --trace 1 --seconds 3
#: --save-records``) of each cell on one TPU v5e, with the spans
SPAN_RECS = ("stablelm-1.6b.decode.spans.rec.json.gz",
             "mistral-nemo-12b.chat.spans.rec.json.gz")


def hand_rec():
    """A 10 s window. The chip is busy 4 s; of its 6 s idle, 0.9 s lie
    under a read-back span, 1.6 s under other engine spans, 2.0 s under no
    span with a request in flight, 1.5 s with none in flight."""
    A, B, C = "python3#7", "python3#8", "python3#9"
    host = [
        # thread A runs the decode pipe: a read-back inside it
        (A, "$executor.py:498 _wait_for_task", 0.0, 10.0),  # not a span
        (A, "pipe.decode", 0.5, 3.5),
        (A, "serve.dispatch", 1.0, 0.2),
        (A, "serve.sync", 1.2, 0.4),
        # thread B overlaps it with a prefill, later a window read-back
        (B, "serve.prefill#req=3#", 1.5, 1.0),
        (B, "serve.window_sync", 6.0, 0.5),
        # the decode worker parks; the second sleep hits the backstop
        (A, "executor.sleep#domain=accel#", 6.5, 0.8),
        (A, "executor.sleep", 9.0, 0.95),
        # a host worker's sleep is not the accelerator's
        (C, "executor.sleep", 4.0, 1.0),
    ]
    ops = [("fusion", 0.0, 1.0), ("fusion", 2.0, 1.0),
           ("paged_attention", 5.0, 0.5), ("fusion", 5.4, 0.6),
           ("fusion", 8.0, 1.0)]
    requests = [{"due": -5.0, "finished": 7.0},
                {"due": 9.5, "finished": None}]
    return {"trace_window_s": 10.0, "busy_s": 4.0, "ops": ops,
            "host": host, "requests": requests}


@pytest.mark.parametrize("name,want", [
    ("idle_sync_frac", 0.09),      # [1.2,1.6] + [6.0,6.5]
    ("idle_host_frac", 0.16),      # [1.0,1.2] + [1.6,2.0] + [3.0,4.0]
    ("idle_handoff_frac", 0.2),    # [4,5] + [6.5,7] + [9.5,10]
])
def test_reader_splits_a_hand_made_window(name, want):
    got = cellrun.read_metric(HERE, name, hand_rec())
    assert got == pytest.approx(want, abs=1e-12)


def test_hand_made_parts_add_up_to_device_idle():
    rec = hand_rec()
    parts = idlesplit.split(rec)
    assert parts["lull"] == pytest.approx(0.15, abs=1e-12)  # [7,8]+[9,9.5]
    idle = cellrun.read_metric(HERE, "device_idle_frac", rec)
    assert idle == pytest.approx(0.6)
    assert sum(parts[k] for k in ("sync", "host", "handoff", "lull")) == \
        pytest.approx(idle, abs=1e-12)
    # accelerator sleeps under the handoff: [6.5,7] + [9.5,9.95]
    assert parts["handoff_sleep_s"] == pytest.approx(0.95, abs=1e-12)
    assert parts["backstop_sleeps"] == 1


def test_handoff_reader_notes_the_sleep_and_the_lull():
    rec = hand_rec()
    cellrun.read_metric(HERE, "idle_handoff_frac", rec)
    note, = rec["notes"]
    assert note.startswith("idle_handoff_frac: 0.95 s of it under an "
                           "accel executor.sleep, 1 sleeps")
    assert note.endswith("lull (idle, no request in flight) 0.15 of the "
                         "window")


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_serve_spans(name):
    rec = hand_rec()
    rec["host"] = [h for h in rec["host"] if not h[1].startswith("serve.")]
    assert cellrun.read_metric(HERE, name, rec) is None
    # the chip trace recorded before the program had spans
    old = tracereduce.load_records(
        str(HERE / "testdata" / "stablelm-1.6b.decode.rec.json.gz"))
    assert cellrun.read_metric(HERE, name, old) is None


def test_interval_arithmetic_matches_a_grid():
    """union / intersect / subtract against the same sets on a grid."""
    rng = random.Random(7)
    for _ in range(200):
        def draw():
            out = []
            for _ in range(rng.randrange(6)):
                s = rng.randrange(40)
                out.append((s, s + rng.randrange(1, 12)))
            return out
        a, b = idlesplit.union(draw()), idlesplit.union(draw())

        def cells(iv):
            return {t for s, e in iv for t in range(s, e)}
        assert all(x[1] < y[0] for x, y in zip(a, a[1:]))
        assert cells(idlesplit.intersect(a, b)) == cells(a) & cells(b)
        assert cells(idlesplit.subtract(a, b)) == cells(a) - cells(b)
        assert idlesplit.length(idlesplit.subtract(a, b)) == \
            len(cells(a) - cells(b))


@pytest.mark.parametrize("fname", SPAN_RECS)
def test_chip_window_parts_add_up_to_device_idle(fname):
    rec = tracereduce.load_records(str(HERE / "testdata" / fname))
    vals = {n: cellrun.read_metric(HERE, n, dict(rec)) for n in READERS}
    assert all(v is not None and v >= 0.0 for v in vals.values())
    lull = idlesplit.split(rec)["lull"]
    idle = cellrun.read_metric(HERE, "device_idle_frac", dict(rec))
    assert abs(sum(vals.values()) + lull - idle) <= 0.002
