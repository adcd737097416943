"""The float32 reference against the program at smoke size on the CPU:
prefill logits, and tokens served through ``ServeEngine`` (window-0
prefill, paged prefill windows, paged decode with table growth)."""
import dataclasses

import jax
import numpy as np
import pytest

import cellrun
import loadgen
import reference
from benchkit import tiny_config

SEED = 2 ** 31 + 99


@pytest.fixture(scope="module")
def tiny():
    conf = tiny_config()
    cfg = cellrun.build_config(conf)
    return conf, cfg, cellrun.make_weights(cfg, SEED)


def test_reference_matches_program_prefill_logits(tiny):
    from repro.models import lm
    conf, cfg, params = tiny
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 24)).astype(np.int32)
    ref = reference.logits(conf, SEED, toks)
    # the program in float32 arithmetic over the same bf16 weights
    f32 = dataclasses.replace(cfg, compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        got, _ = lm.forward(f32, params, toks)
    got = np.asarray(got)[..., :cfg.vocab_size]
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 1e-4 * scale
    # and in its served bf16 arithmetic, within bf16 rounding
    got16, _ = lm.forward(cfg, params, toks)
    err = np.abs(np.asarray(got16)[..., :cfg.vocab_size] - ref).max()
    assert 1e-4 * scale < err <= 5e-2 * scale


def test_served_tokens_agree_with_reference(tiny):
    from repro.serve.engine import ServeEngine
    conf, cfg, params = tiny
    eng = ServeEngine(cfg, params, **conf["engine"])
    rng = np.random.default_rng(1)
    # prompts of one and of several prefill windows; outputs that grow
    # the block table
    lens = [5, 40, 70, 130]
    prompts = [loadgen.prompt_tokens(rng, n, cfg.vocab_size) for n in lens]
    reqs = [eng.submit(p, max_new=20) for p in prompts]
    outs = [np.asarray(eng.result(r, timeout=300)) for r in reqs]
    assert eng.stats["prefill_windows"] > 0
    eng.close()
    gaps, low = reference.reference_gaps(conf, SEED, list(zip(prompts, outs)),
                                         control=True)
    assert gaps.shape == (4, 20)
    assert gaps.max() <= 1e-2
    # a token altered where it is produced shows as a wide gap
    bad = [o.copy() for o in outs]
    bad[2][7] = (bad[2][7] + 1) % cfg.vocab_size
    g2, _ = reference.reference_gaps(conf, SEED, list(zip(prompts, bad)))
    assert g2[2, 7] > 10 * max(gaps.max(), 1e-3)
    assert np.isfinite(low).all()
