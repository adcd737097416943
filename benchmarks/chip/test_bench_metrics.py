"""Each per-layer reader against its known value on a trace recorded on the
chip: ``testdata/stablelm-1.6b.decode.rec.json.gz`` holds the reduced
records of a 3-second traced window of ``stablelm-1.6b.decode`` on one
TPU v5e (8 rows, Pallas paged decode, 1,366-block pool)."""
import pytest

import cellrun
import tracereduce
from benchkit import HERE

REC = HERE / "testdata" / "stablelm-1.6b.decode.rec.json.gz"

#: the values each reader gives on that trace
KNOWN = {
    "prefill_window_ms": 69.01212650000001,
    "decode_step_ms": 70.37146285,
    "decode_mfu": 0.12239262457199498,
    "paged_decode_roofline": 0.5332734659559906,
    "device_idle_frac": 0.015841354570744426,
    "peak_hbm_gib": 7.366670608520508,
    # no request fell due in 3 seconds of a closed loop: nothing to read
    "queue_wait_p90_s": None,
}


@pytest.fixture(scope="module")
def rec():
    return tracereduce.load_records(str(REC))


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_reader_gives_its_known_value(rec, name):
    got = cellrun.read_metric(HERE, name, dict(rec))
    if KNOWN[name] is None:
        assert got is None
    else:
        assert got == pytest.approx(KNOWN[name], rel=1e-9)


def test_known_values_from_the_raw_records(rec):
    decode = [m for m in rec["modules"] if m[0] == "jit__decode_paged_impl"]
    # five whole 8-step chunks lie inside the window
    assert [m[3] for m in decode] == [1.0] * 5
    assert sum(m[2] for m in decode) / 40 * 1e3 == \
        pytest.approx(KNOWN["decode_step_ms"])
    assert 1 - rec["busy_s"] / rec["trace_window_s"] == \
        pytest.approx(KNOWN["device_idle_frac"])
    assert rec["memory_peak_bytes"] / 2 ** 30 == \
        pytest.approx(KNOWN["peak_hbm_gib"])


def test_roofline_is_bandwidth_bound_and_under_100(rec):
    r = dict(rec)
    v = cellrun.read_metric(HERE, "paged_decode_roofline", r)
    assert 0 < v < 100
    assert r["notes"][-1].startswith("paged_decode_roofline: bandwidth bound")


def test_a_reader_with_nothing_to_read_is_silent(rec):
    r = dict(rec, ops=[], modules=[])
    for name in ("paged_decode_roofline", "decode_mfu", "decode_step_ms",
                 "prefill_window_ms"):
        assert cellrun.read_metric(HERE, name, r) is None


def test_queue_wait_reads_the_window(rec):
    r = dict(rec, window_metrics={"queue_wait_p90_s": 0.25})
    assert cellrun.read_metric(HERE, "queue_wait_p90_s", r) == 0.25
    r = dict(rec, window_metrics={"queue_wait_p90_s": float("inf")})
    assert cellrun.read_metric(HERE, "queue_wait_p90_s", r) is None


def test_breakdown_names_programs_and_host_frames(rec):
    b = tracereduce.breakdown(rec)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    top, secs = b["device_ops"][0]
    assert top == "jit__decode_paged_impl:paged_attention"
    assert secs == pytest.approx(2.642738128)
    assert not any(n.endswith(":while") for n, _ in b["device_ops"])
    assert all(n.startswith("$") for n, _ in b["idle_gaps"])
