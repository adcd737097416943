"""Helpers of the benchmark's CPU tests: a smoke-size configuration and
its mixes, and checkout copies to add cells to the way a later change
would — new files and new manifest entries, nothing edited."""
import json
import os
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]

#: the smoke-size stand-in for stablelm-1.6b: every width cut, same code
TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512,
}
TINY_ENGINE = {"max_batch": 4, "kv_blocks": 256, "block_size": 8,
               "max_seq_len": 256}
TINY_MIXES = {
    "tinyclosed": {"loop": "closed", "clients": 4,
                   "prompt": {"dist": "uniform", "lo": 8, "hi": 40},
                   "output": {"dist": "uniform", "lo": 8, "hi": 24},
                   "block": 8},
    "tinyopen": {"loop": "open", "rate_per_s": 4.0,
                 "arrival": {"dist": "gamma", "cv": 2.0},
                 "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.8,
                            "lo": 8, "hi": 60},
                 "output": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                            "lo": 4, "hi": 20},
                 "lead_s": 1, "block": 8},
}


def tiny_config(max_logit_gap: float = 0.01) -> dict:
    conf = json.loads((HERE / "configs" / "stablelm-1.6b.json").read_text())
    conf.update(TINY_MODEL)
    fields = {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "d_ff": 96,
              "vocab_size": 512, "param_dtype": "bfloat16"}
    conf["program"] = {"arch": "stablelm-1.6b", "fields": fields}
    conf["engine"] = dict(TINY_ENGINE)
    conf["check"] = {"max_logit_gap": max_logit_gap, "served_tokens": 120,
                     "max_requests": 6}
    return conf


def tiny_mesh_config() -> dict:
    """The smoke-size configuration on a ``model=4`` mesh: 8 query and 4
    KV heads, so that four devices each hold one KV head."""
    conf = tiny_config()
    conf.update(num_attention_heads=8, num_key_value_heads=4)
    conf["program"]["fields"].update(num_heads=8, num_kv_heads=4)
    conf["mesh"] = {"data": 1, "model": 4}
    return conf


def add_cell(root: Path, config: str, conf: dict, mixes: dict,
             chips: int = 1) -> None:
    """Add a configuration, its traffic mixes and one cell per mix on
    ``chips`` chips to the checkout at ``root``: new files and appended
    manifest entries only."""
    bench = root / "benchmarks" / "chip"
    (bench / "configs" / f"{config}.json").write_text(json.dumps(conf))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": config, "source": "smoke size",
                           "file": f"benchmarks/chip/configs/{config}.json",
                           "reduced": [], "why": "CPU tests"})
    for mix, spec in mixes.items():
        (bench / "traffic" / f"{mix}.json").write_text(json.dumps(spec))
        man["workloads"].append({"name": f"{config}.{mix}", "config": config,
                                 "traffic": mix, "chips": chips,
                                 "why": "CPU tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def copy_checkout(dst: Path, with_src: bool = True) -> Path:
    """The files a checkout of the benchmark holds, under ``dst``."""
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(HERE, dst / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    if with_src:
        os.symlink(REPO / "src", dst / "src")
    return dst
