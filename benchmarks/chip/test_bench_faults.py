"""Whole smoke-size runs with the timed path broken underneath, and the
lower-precision control: each must come out not ``correct``.

The harness's look for a chip is skipped (``need_chip=False``); the rest
of the run — warm-up, traffic, window, drain, check — is the benchmark's
own. The faults are planted in the program's decode chunk
(``repro.models.lm.decode_chunk_paged``), which the engine traces when it
first compiles, so each test builds its engine after planting.
"""
import functools

import jax.numpy as jnp

import cellrun

SEED = 2 ** 31 + 4242
CELL = "tiny.tinyclosed"


def _run(root, **kw):
    return cellrun.run(root, CELL, SEED, 2, trace=False, need_chip=False,
                       log=lambda *_: None, **kw)


def _plant(monkeypatch, wrap):
    from repro.models import lm
    orig = lm.decode_chunk_paged
    monkeypatch.setattr(lm, "decode_chunk_paged",
                        functools.wraps(orig)(wrap(orig)))


def test_sound_run_is_correct(tiny_root):
    out = _run(tiny_root)
    assert out["correct"] is True


def test_token_altered_where_produced(tiny_root, monkeypatch):
    def wrap(orig):
        def chunk(cfg, *a, **kw):
            pkv, carry, toks = orig(cfg, *a, **kw)
            toks = toks.at[:, -1].set((toks[:, -1] + 1) % cfg.vocab_size)
            return pkv, carry, toks
        return chunk
    _plant(monkeypatch, wrap)
    out = _run(tiny_root)
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_step_returns_its_state_unchanged(tiny_root, monkeypatch):
    def wrap(orig):
        def chunk(cfg, params, pool_kv, *a, **kw):
            _, carry, toks = orig(cfg, params, pool_kv, *a, **kw)
            return pool_kv, carry, toks      # the KV writes are lost
        return chunk
    _plant(monkeypatch, wrap)
    out = _run(tiny_root)
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_control_fails_the_limit(tiny_root):
    """The fp8 control in the program's place comes out not correct."""
    out = _run(tiny_root, control=True)
    assert out["correct"] is False
    ctl = out["checks"]["logit_gap"]
    assert ctl["value"] > ctl["limit"]


def test_counts_are_checked(tiny_root, monkeypatch):
    """A served answer one token short fails the length check and the
    count of tokens against the engine's counter."""
    from repro.serve.engine import ServeEngine
    orig = ServeEngine.result

    def short(self, req, *a, **kw):
        return jnp.asarray(orig(self, req, *a, **kw))[:-1]
    monkeypatch.setattr(ServeEngine, "result", short)
    out = _run(tiny_root)
    assert out["correct"] is False
    for field in ("tokens_counted", "requests_wrong_length"):
        c = out["checks"][field]
        assert c["value"] != c["limit"]
