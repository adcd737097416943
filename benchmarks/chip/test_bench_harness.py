"""CPU tests of the benchmark harness: manifest, traffic, the refusal to run
without a chip, and a whole run of a smoke-size cell."""
import json
import os
import re
import subprocess
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import cellrun
import loadgen
import opcount
from benchkit import HERE, REPO, TINY_MIXES, add_cell, copy_checkout, \
    tiny_config

BIG_SEED = 2 ** 31 + 12345
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _manifest():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_manifest_names_files_that_exist():
    man = _manifest()
    assert man["paths"] == ["benchmarks/chip"]
    assert man["command"] == ["python3", "benchmarks/chip/run_cell.py"]
    for c in man["configs"]:
        assert NAME.match(c["name"])
        conf = json.loads((REPO / c["file"]).read_text())
        published = conf["published"]
        assert all(k in published for k in c["reduced"]), c["name"]
    for w in man["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cell = cellrun.load_cell(REPO, w["name"])
        assert cell["per_layer"] and len(cell["end_to_end"]) >= 2
    e2e = {m["name"] for m in man["end_to_end"]}
    assert "setup_s" in e2e
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()


def test_every_cell_reads_its_metrics():
    man = _manifest()
    for w in man["workloads"]:
        cell = cellrun.load_cell(REPO, w["name"])
        names = [m["name"] for m in cell["per_layer"]]
        listed = [m["name"] for m in man["per_layer"]
                  if w["name"] in m["workloads"]]
        assert names == listed and names, (w["name"], names)
        for name in names:
            assert (HERE / "metrics" / f"{name}.py").is_file(), name
    cells = {w["name"] for w in man["workloads"]}
    for m in man["per_layer"]:
        assert set(m["workloads"]) <= cells, m["name"]


@pytest.mark.parametrize("mix", ["decode", "chat"])
def test_traffic_is_deterministic_from_the_seed(mix):
    spec = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
    if spec["loop"] == "closed":
        def make(seed):
            qs = loadgen.closed_loop_requests(spec, seed, 1000, 8)
            return [(0.0, p.tolist(), m) for q in qs for p, m in q]
    else:
        def make(seed):
            return [(round(t, 9), p.tolist(), m) for t, p, m, _ in
                    loadgen.open_loop_schedule(spec, seed, 1000, 30)]
    a, b, c = make(BIG_SEED), make(BIG_SEED), make(BIG_SEED + 1)
    assert a == b
    assert a != c

    # another seed draws other prompt tokens, and offers the same work at
    # the same times
    def work(reqs):
        return [(t, len(p), m) for t, p, m in reqs]
    assert work(a) == work(c)
    assert all(pa != pc for (_, pa, _), (_, pc, _) in zip(a, c))
    sizes = Counter(len(p) for _, p, _ in a)
    assert len(sizes) > 1


def test_open_loop_rate_and_lead_in():
    spec = TINY_MIXES["tinyopen"]
    sched = loadgen.open_loop_schedule(spec, BIG_SEED, 512, 10)
    lead = [t for t, _, _, w in sched if not w]
    win = [t for t, _, _, w in sched if w]
    assert len(lead) == round(spec["rate_per_s"] * spec["lead_s"])
    assert len(win) == round(spec["rate_per_s"] * 10)
    assert all(0 <= t < spec["lead_s"] for t in lead)
    assert all(spec["lead_s"] <= t < spec["lead_s"] + 10 for t in win)


def test_size_quantiles_stay_in_range():
    for mix in ("decode", "chat"):
        spec = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
        for key in ("prompt", "output"):
            q = loadgen.quantiles(spec[key], 64)
            assert q.min() >= spec[key]["lo"] and q.max() <= spec[key]["hi"]


def test_nearest_rank_counts_failures_as_endless():
    assert loadgen.nearest_rank([1.0, 2.0, 3.0, float("inf")], 0.5) == 2.0
    assert loadgen.nearest_rank([1.0] * 9 + [float("inf")], 0.9) == 1.0
    assert loadgen.nearest_rank([1.0] * 8 + [float("inf")] * 2, 0.9) \
        == float("inf")


def test_adding_a_cell_edits_no_file(tmp_path, monkeypatch):
    root = copy_checkout(tmp_path, with_src=False)
    bench = root / "benchmarks" / "chip"
    monkeypatch.syspath_prepend(str(bench))

    def digest():
        return {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    before = digest()
    # a configuration of a model family of its own: a new family module
    (bench / "tiny_family.py").write_text(
        (HERE / "dense_decoder.py").read_text())
    add_cell(root, "tiny", dict(tiny_config(), family="tiny_family"),
             {"tinyclosed": TINY_MIXES["tinyclosed"]})
    reader = bench / "metrics" / "requests_in_window.py"
    reader.write_text("def read(rec):\n"
                      "    return float(len(rec['requests']))\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["per_layer"].append({"name": "requests_in_window", "unit": "1",
                             "better": "higher", "source": "program_span",
                             "layer": "scheduler", "moves": "ttft_p90_s",
                             "workloads": ["tiny.tinyclosed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    after = digest()
    assert all(after[p] == b for p, b in before.items())
    cell = cellrun.load_cell(root, "tiny.tinyclosed")
    assert cell["config"]["hidden_size"] == 64
    assert cellrun.build_config(cell["config"]).d_model == 64
    assert opcount.family(cell["config"]).__file__ == str(
        bench / "tiny_family.py")
    assert cell["mix"]["clients"] == 4
    names = [m["name"] for m in cell["per_layer"]]
    assert "requests_in_window" in names
    assert cellrun.read_metric(bench, "requests_in_window",
                               {"requests": [{}, {}]}) == 2.0
    # the cells that were there are untouched by the new metric
    old = cellrun.load_cell(root, "stablelm-1.6b.decode")
    assert "requests_in_window" not in [m["name"] for m in old["per_layer"]]


def test_unknown_device_kind_is_an_error(monkeypatch):
    class Fake:
        platform, device_kind = "tpu", "TPU v99"
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    with pytest.raises(cellrun.NoChip, match="not in peaks.json"):
        cellrun.check_device(peaks, 1, need_chip=True)
    Fake.device_kind = "TPU v5 lite"
    devs, peak = cellrun.check_device(peaks, 1, need_chip=True)
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cellrun.NoChip, match="needs 4 chips"):
        cellrun.check_device(peaks, 4, need_chip=True)


def _run_cell(cwd: Path, root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "benchmarks/chip/run_cell.py"),
         "--workload", "stablelm-1.6b.decode", "--seed", str(BIG_SEED),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_nonzero_without_a_result(tmp_path):
    root = copy_checkout(tmp_path)
    p = _run_cell(root, root)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    root = copy_checkout(tmp_path, with_src=False)
    p = _run_cell(root, root)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell", ["tiny.tinyclosed", "tiny.tinyopen"])
def test_smoke_cell_runs_correct(tiny_root, cell):
    out = cellrun.run(tiny_root, cell, BIG_SEED, 2, trace=False,
                      need_chip=False, log=lambda *_: None)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "ttft_p90_s",
                                   "tpot_p90_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    gap = out["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert np.isfinite(out["metrics"]["ttft_p90_s"]["value"])


def test_traced_run_traces_the_last_trace_s(tmp_path):
    """A configuration's ``trace_s`` shortens the traced part of the
    window to its last seconds; the window itself keeps its length."""
    root = copy_checkout(tmp_path)
    add_cell(root, "tinyshort", dict(tiny_config(), trace_s=1),
             {"tinyopen": TINY_MIXES["tinyopen"]})
    logs = []
    out = cellrun.run(root, "tinyshort.tinyopen", BIG_SEED, 3, trace=True,
                      need_chip=False, log=logs.append)
    assert out["correct"] is True
    assert out["device"]["window_s"] == pytest.approx(1.0, abs=0.1)
    line, = [m for m in logs if m.startswith("traced the window's last ")]
    traced = float(line.split()[4])
    assert traced == pytest.approx(1.0, abs=0.1)
    assert any(m.startswith("setup ") and "; window 3.0" in m for m in logs)


def test_trace_s_must_be_positive(tmp_path):
    root = copy_checkout(tmp_path, with_src=False)
    add_cell(root, "tinybad", dict(tiny_config(), trace_s=0),
             {"tinyopen": TINY_MIXES["tinyopen"]})
    with pytest.raises(cellrun.CellError, match="trace_s 0"):
        cellrun.load_cell(root, "tinybad.tinyopen")


def test_run_frees_only_its_own_arrays(tiny_root):
    """A run deletes what it left on the device before the reference
    runs, and no array that was live before it started."""
    import jax.numpy as jnp
    mine = jnp.arange(7)
    out = cellrun.run(tiny_root, "tiny.tinyclosed", BIG_SEED, 1,
                      trace=False, need_chip=False, log=lambda *_: None)
    assert out["correct"] is True
    assert not mine.is_deleted()
    assert int(mine.sum()) == 21
