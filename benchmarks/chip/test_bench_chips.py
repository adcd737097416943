"""Readers that count per chip, and the trace reduction over the cell's
chips: one chip's readings are those of the records committed under
``testdata/`` (recorded on one TPU v5e before records carried ``chips``),
to the last digit; a four-chip record divides the work by its chips;
``collective_frac`` on hand-made windows."""
import pytest

import cellrun
import tracereduce
from benchkit import HERE

#: every reader on each committed record, as the readers gave them before
#: records carried ``chips``
KNOWN = {
    "mistral-nemo-12b.chat.spans.rec.json.gz": {
        "decode_mfu": 0.30781102292870177,
        "decode_step_ms": 25.295667361204366,
        "device_idle_frac": 0.04322730290351262,
        "idle_handoff_frac": 0.0005704380488397023,
        "idle_host_frac": 0.03224962414267202,
        "idle_sync_frac": 0.01040724071200103,
        "paged_decode_roofline": 0.48468262831045794,
        "peak_hbm_gib": 8.268599033355713,
        "prefill_window_ms": 49.16333389400306,
        "queue_wait_p90_s": 0.18922933934479147,
    },
    "stablelm-1.6b.decode.rec.json.gz": {
        "decode_mfu": 0.12239262457199498,
        "decode_step_ms": 70.37146285,
        "device_idle_frac": 0.015841354570744426,
        "idle_handoff_frac": None,
        "idle_host_frac": None,
        "idle_sync_frac": None,
        "paged_decode_roofline": 0.5332734659559906,
        "peak_hbm_gib": 7.366670608520508,
        "prefill_window_ms": 69.01212650000001,
        "queue_wait_p90_s": None,
    },
    "stablelm-1.6b.decode.spans.rec.json.gz": {
        "decode_mfu": 0.10967127789171296,
        "decode_step_ms": 66.67830605,
        "device_idle_frac": 0.01805640861141955,
        "idle_handoff_frac": 0.0011019068420737,
        "idle_host_frac": 0.01131481627227931,
        "idle_sync_frac": 0.00563968549706666,
        "paged_decode_roofline": 0.33605063120948364,
        "peak_hbm_gib": 4.138154983520508,
        "prefill_window_ms": 69.93545050000002,
        "queue_wait_p90_s": 0.7433460270000012,
    },
}
CASES = [(f, n) for f in sorted(KNOWN) for n in sorted(KNOWN[f])]
PROGRAM = "jit__decode_paged_impl"


def _rec(fname):
    return tracereduce.load_records(str(HERE / "testdata" / fname))


def test_known_values_cover_every_reader_but_the_mesh_one():
    readers = {p.stem for p in (HERE / "metrics").glob("*.py")}
    for fname, vals in KNOWN.items():
        assert set(vals) == readers - {"collective_frac"}, fname


@pytest.mark.parametrize("fname,name", CASES)
def test_one_chip_reads_as_before(fname, name):
    rec = _rec(fname)
    assert "chips" not in rec
    before = cellrun.read_metric(HERE, name, dict(rec))
    after = cellrun.read_metric(HERE, name, dict(rec, chips=1))
    assert before == after == KNOWN[fname][name]


@pytest.mark.parametrize("fname", sorted(KNOWN))
def test_four_chips_share_the_work(fname):
    rec = _rec(fname)
    for name in ("decode_mfu", "paged_decode_roofline"):
        one = cellrun.read_metric(HERE, name, dict(rec))
        four = cellrun.read_metric(HERE, name, dict(rec, chips=4))
        assert four == pytest.approx(one / 4, rel=1e-12)
    # a step's time and the first chip's idle do not divide
    for name in ("decode_step_ms", "device_idle_frac"):
        assert cellrun.read_metric(HERE, name, dict(rec, chips=4)) == \
            KNOWN[fname][name]


def _window(ops, modules):
    return {"ops": ops, "modules": modules}


def test_collective_frac_counts_collectives_inside_the_decode_program():
    modules = [(PROGRAM, 1.0, 2.0, 1.0), ("jit__prefill_window_impl", 4.0,
                                          1.0, 1.0), (PROGRAM, 6.0, 2.0, 1.0)]
    ops = [("fusion", 1.0, 0.5),
           ("all-gather", 1.5, 0.25),               # in decode
           ("all-gather-start", 2.0, 0.05),         # in decode
           ("all-gather-done", 2.05, 0.15),         # in decode
           ("all-gather", 4.2, 0.5),                # in prefill: out
           ("all-reduce", 5.5, 0.1),                # between programs: out
           ("reduce-scatter", 6.5, 0.1),            # in decode
           ("collective-permute-done", 7.0, 0.05),  # in decode
           ("paged_attention", 7.5, 0.4)]
    rec = _window(ops, modules)
    got = cellrun.read_metric(HERE, "collective_frac", rec)
    assert got == pytest.approx((0.25 + 0.05 + 0.15 + 0.1 + 0.05) / 4.0,
                                abs=1e-12)
    note, = rec["notes"]
    assert note.startswith("collective_frac: all-gather 0.25 s, "
                           "all-gather-done 0.15 s, all-gather-start 0.05 s,")
    assert note.endswith("of 4 s of jit__decode_paged_impl")


def test_collective_frac_is_silent_without_collectives():
    modules = [(PROGRAM, 0.0, 1.0, 1.0), ("jit__prefill_impl", 2.0, 1.0,
                                          1.0)]
    ops = [("fusion", 0.0, 0.9), ("all-gather", 2.1, 0.5)]
    assert cellrun.read_metric(HERE, "collective_frac",
                               _window(ops, modules)) is None
    assert cellrun.read_metric(HERE, "collective_frac",
                               _window([], [])) is None
    # the one-chip traces hold no collective at all
    for fname in KNOWN:
        assert cellrun.read_metric(HERE, "collective_frac",
                                   _rec(fname)) is None


def test_window_reduction_keeps_the_cell_chips_only():
    """Four device planes, of which a two-chip cell owns the two lowest:
    the ops and programs are the first's, busy time the mean of both."""
    ms = 1_000_000

    def plane(busy_ms):
        return {"ops": [("fusion", 100 * ms, busy_ms * ms)],
                "modules": [(PROGRAM, 100 * ms, busy_ms * ms)]}
    devices = {2: plane(500), 0: plane(400), 1: plane(200), 3: plane(0)}
    host = [("python3#1", "serve.sync", 50 * ms, 100 * ms)]
    rec = tracereduce.reduce_window(0, 1000 * ms, host, devices, 2)
    assert rec["chips"] == 2 and rec["n_devices"] == 2
    assert rec["busy_s"] == pytest.approx(0.3)
    assert rec["ops"] == [("fusion", pytest.approx(0.1), pytest.approx(0.4))]
    assert rec["modules"][0][3] == 1.0
    assert rec["host"][0][2:] == (pytest.approx(0.05), pytest.approx(0.1))
    one = tracereduce.reduce_window(0, 1000 * ms, host, devices, 1)
    assert one["busy_s"] == pytest.approx(0.4) and one["chips"] == 1
    # a window edge cuts a program run: its share is the part inside
    cut = tracereduce.reduce_window(300 * ms, 1000 * ms, host, devices, 1)
    assert cut["modules"][0][3] == pytest.approx(0.5)
