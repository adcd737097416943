"""The hybrid decoder family (Nemotron-H) at smoke size on the CPU: the
token-by-token float32 reference against the program — prefill windows at
every boundary, then decode, through the paged programs; tokens served by
``ServeEngine`` with preemption and slot reuse — and the family's counts at
the configuration file's sizes, and a whole run of a smoke-size cell.

The program runs here in float32 arithmetic over the same bf16 weights as
the reference, so the two differ only in the order of their sums (the
chunked SSD form against the recurrence, one matmul shape against
another): a relative logit error of 1e-4 of the logits' scale is far above
that (about 1e-6 measured) and far below what a missing state carry or a
misplaced window boundary makes (0.05 and more)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cellrun
import hybrid_decoder as fam
import loadgen
import opcount
import reference_hybrid
from benchkit import HERE, add_cell, copy_checkout

SEED = 2 ** 31 + 77
BLOCK = 8
#: the program's logits against the reference's, relative to their scale
LOGIT_TOL = 1e-4

TINY_MODEL = {
    "hidden_size": 64, "attention_head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 96, "vocab_size": 512,
    "hybrid_override_pattern": "M-*M-", "num_hidden_layers": 5,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2,
}


def tiny_hybrid_config(max_logit_gap: float = 0.01) -> dict:
    conf = json.loads((HERE / "configs" / "nemotron-h-47b.json").read_text())
    conf.update(TINY_MODEL)
    conf["program"] = {"arch": "nemotron-h-47b", "fields": {
        "num_layers": 5, "layer_pattern": "M-*M-", "d_model": 64,
        "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "d_ff": 96,
        "vocab_size": 512, "ssm_state": 16, "ssm_head_dim": 16,
        "ssm_groups": 2, "ssm_chunk": 8, "param_dtype": "bfloat16"}}
    conf["engine"] = {"max_batch": 4, "kv_blocks": 256, "block_size": BLOCK,
                      "max_seq_len": 256}
    conf["check"] = {"max_logit_gap": max_logit_gap, "served_tokens": 120,
                     "max_requests": 6}
    conf.pop("trace_s")
    return conf


@pytest.fixture(scope="module")
def tiny():
    conf = tiny_hybrid_config()
    cfg = cellrun.build_config(conf)
    return conf, cfg, cellrun.make_weights(cfg, SEED)


def _f32(cfg):
    return dataclasses.replace(cfg, compute_dtype="float32")


def _paged_logits(cfg, params, tokens, windows, n_decode):
    """The program's paged path for one sequence: its prompt through
    prefill windows that end at ``windows`` (cumulative lengths), then
    ``n_decode`` greedy decode steps. Returns (every decode step's logits
    (n_decode, vocab), each window's first token, the decode tokens, the
    final ``(pool, (conv, h))``)."""
    from repro.models import lm
    from repro.serve.kvcache import init_kv_pool, init_recurrent_state
    B, C = 1, max(b - a for a, b in zip([0] + windows, windows))
    mb = 256 // BLOCK
    tables = jnp.asarray(np.arange(1, mb + 1, dtype=np.int32)[None])
    cache = (init_kv_pool(cfg, mb + 1, BLOCK), init_recurrent_state(cfg, B))
    out = []
    for a, b in zip([0] + windows, windows):
        toks = np.zeros((B, C), np.int32)
        toks[0, :b - a] = tokens[a:b]
        valid = np.arange(C)[None] < b - a
        first, cache = lm.prefill_window_paged(
            cfg, params, cache, tables, jnp.asarray(toks),
            jnp.asarray([a], jnp.int32), jnp.asarray(valid),
            jnp.asarray([b - a - 1], jnp.int32))
        out.append(first)
    toks, tok, ln = [], out[-1], jnp.asarray([windows[-1]], jnp.int32)
    logits = []
    for _ in range(n_decode):
        lg, cache = lm.decode_step_paged(cfg, params, cache, tables, ln, tok,
                                         jnp.asarray([True]), impl="gather")
        logits.append(np.asarray(lg)[0, :cfg.vocab_size])
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        toks.append(int(tok[0]))
        ln = ln + 1
    return (np.stack(logits) if logits else None, [int(f[0]) for f in out],
            toks, cache)


def test_windows_then_decode_match_the_reference(tiny):
    conf, cfg, params = tiny
    cfg = _f32(cfg)
    rng = np.random.default_rng(0)
    prompt = loadgen.prompt_tokens(rng, 29, cfg.vocab_size)
    # windows of 8, 3, 1, 17: boundaries inside and at a block, one token
    logits, firsts, dec, _ = _paged_logits(cfg, params, prompt,
                                           [8, 11, 12, 29], 6)
    seq = np.concatenate([prompt, [firsts[-1]] + dec[:-1]]).astype(np.int32)
    ref = reference_hybrid.logits(conf, SEED, seq[None])[0]
    scale = np.abs(ref).max()
    # each window's first token is the reference's greedy choice at the
    # window's last prompt position
    for end, f in zip([8, 11, 12, 29], firsts):
        assert f == int(np.argmax(ref[end - 1]))
    err = np.abs(logits - ref[29:35]).max()
    assert err <= LOGIT_TOL * scale, err / scale


def test_state_across_a_window_boundary_equals_the_whole_prompt(tiny):
    from repro.models import lm
    _, cfg, params = tiny
    cfg = _f32(cfg)
    prompt = loadgen.prompt_tokens(np.random.default_rng(1), 24,
                                   cfg.vocab_size)
    _, _, _, split = _paged_logits(cfg, params, prompt, [5, 24], 0)
    _, _, _, whole = _paged_logits(cfg, params, prompt, [24], 0)
    _, ref = lm.prefill(cfg, params, jnp.asarray(prompt[None]), max_len=24)
    for a, b, c in zip(split[1], whole[1], ref["ssm"]):
        b, c = np.asarray(b, np.float32), np.asarray(c, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(b, c, atol=1e-5, rtol=1e-4)


def test_row_by_row_window_attention_equals_the_batched(tiny, monkeypatch):
    """A window whose f32 scores pass ``WINDOW_SCORES_BYTES`` attends one
    row at a time (of the cells, only the hybrid's 16 rows x 64 heads x
    8192 keys cross it): two rows at different offsets, each on its own
    pages, with prompts of 24 and 21 tokens, give the same first tokens,
    pool and carried state."""
    from repro.models import attention, lm
    from repro.serve.kvcache import init_kv_pool, init_recurrent_state
    _, cfg, params = tiny
    cfg = _f32(cfg)
    rng = np.random.default_rng(2)
    mb, C = 8, 8
    tables = jnp.asarray(np.arange(1, 2 * mb + 1, dtype=np.int32)
                         .reshape(2, mb))

    def windows():
        cache = (init_kv_pool(cfg, 2 * mb + 1, BLOCK),
                 init_recurrent_state(cfg, 2))
        firsts = []
        for start in ([0, 0], [8, 8], [16, 16]):
            toks = rng_toks[start[0] // C]
            valid = np.ones((2, C), bool)
            valid[1, 5:] = start[0] < 16        # row 1 ends at 21
            first, cache = lm.prefill_window_paged(
                cfg, params, cache, tables, jnp.asarray(toks),
                jnp.asarray(start, jnp.int32), jnp.asarray(valid),
                jnp.asarray([C - 1, 4], jnp.int32))
            firsts.append(np.asarray(first))
        return firsts, cache

    rng_toks = [rng.integers(0, cfg.vocab_size, (2, C)).astype(np.int32)
                for _ in range(3)]
    fa, a = windows()
    monkeypatch.setattr(attention, "WINDOW_SCORES_BYTES", 0)
    fb, b = windows()
    np.testing.assert_array_equal(np.stack(fa), np.stack(fb))
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   atol=1e-5, rtol=1e-4)


def _serve(cfg, params, prompts, max_new, **kw):
    from repro.serve.engine import ServeEngine
    eng = ServeEngine(cfg, params, block_size=BLOCK, max_seq_len=128, **kw)
    try:
        reqs = [eng.submit(p, max_new=max_new) for p in prompts]
        outs = [np.asarray(eng.result(r, timeout=300)) for r in reqs]
        return outs, dict(eng.stats)
    finally:
        eng.close()


def test_preempted_and_reused_rows_serve_the_reference(tiny):
    """Six prompts on two rows (every slot reused), prompts of one to
    several windows, and a sync engine preempting a row every third cycle
    six times: a decoding row saves its state and pages and resumes from
    them (``serve.state_save``), a prefilling one replays. The same tokens
    as six rows that never preempt, and the reference's."""
    from repro.obs import Observability
    conf, cfg, params = tiny
    cfg = _f32(cfg)
    rng = np.random.default_rng(2)
    prompts = [loadgen.prompt_tokens(rng, n, cfg.vocab_size)
               for n in (5, 21, 40, 9, 33, 16)]
    fresh, _ = _serve(cfg, params, prompts, 14, max_batch=6, kv_blocks=64,
                      decode_chunk=4, prefill_chunk=8)
    obs = Observability()
    reused, st = _serve(cfg, params, prompts, 14, max_batch=2, kv_blocks=64,
                        decode_chunk=4, prefill_chunk=8, obs=obs,
                        fault_inject="preempt:every=3,n=6")
    names = [sp[0] for sp in obs.tracer.spans()]
    assert st["preempted"] == 6 and "serve.state_save" in names
    assert names.count("serve.state_seat") >= 3     # six seats, two rows
    m = obs.metrics
    assert m.get("serve.recurrent_windows").value \
        == st["recurrent_windows"] == st["prefill_windows"] > 0
    # 2 Mamba layers x 2 rows x (a 3 x 192 conv window and an 8 x 16 x 16
    # state), float32 here
    assert m.get("serve.recurrent_state_bytes").value \
        == 2 * 2 * (3 * 192 + 8 * 16 * 16) * 4
    for a, b in zip(fresh, reused):
        np.testing.assert_array_equal(a, b)
    gaps, _ = reference_hybrid.reference_gaps(
        conf, SEED, list(zip(prompts, reused)), pad_to=64)
    assert gaps.max() <= 1e-3


def test_prefix_cache_is_refused(tiny):
    from repro.serve.engine import ServeEngine
    _, cfg, params = tiny
    with pytest.raises(ValueError, match="prefix cache is refused"):
        ServeEngine(cfg, params, prefix_cache=True)


def test_grouped_mamba2_at_one_group_equals_zamba2s():
    """The grouped Mamba-2 with one group is zamba2's: against the
    ungrouped SSD form it replaced (kept here as written before groups)."""
    from repro.configs import get_config
    from repro.models import lm, mamba
    cfg = dataclasses.replace(get_config("zamba2-1.2b").smoke(),
                              compute_dtype="float32")
    p = jax.tree_util.tree_map(lambda t: t[0, 0], lm.init_params(
        cfg, jax.random.PRNGKey(3))["gblocks"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    got = mamba.mamba_forward(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_ungrouped_m2(p, x, cfg)),
                               atol=1e-5, rtol=1e-5)


def _ungrouped_m2(p, x, cfg):
    from repro.models.layers import rms_norm
    from repro.models.mamba import _causal_conv
    B, S, _ = x.shape
    dI, N, nh, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, \
        cfg.ssm_head_dim
    z, xh, Bc, Cc, dt = jnp.split(x @ p["in_proj"],
                                  [dI, 2 * dI, 2 * dI + N, 2 * dI + 2 * N],
                                  axis=-1)
    xbc = jax.nn.silu(_causal_conv(jnp.concatenate([xh, Bc, Cc], -1),
                                   p["conv_w"], p["conv_b"]))
    xh, Bc, Cc = jnp.split(xbc, [dI, dI + N], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xhh = xh.reshape(B, S, nh, hp)
    L = jnp.cumsum(dt * A, axis=1)
    seg = L[:, :, None, :] - L[:, None, :, :]
    tri = jnp.tril(jnp.ones((S, S), bool))
    decay = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
    w = jnp.einsum("btn,bsn->bts", Cc, Bc)[..., None] * decay \
        * dt[:, None, :, :]
    y = jnp.einsum("btsh,bshp->bthp", w, xhh) + p["ssm_D"][:, None] * xhh
    y = rms_norm(y.reshape(B, S, dI) * jax.nn.silu(z), p["ssm_norm"],
                 cfg.rms_eps)
    return y @ p["out_proj"]


def _dims(model):
    assert opcount.family(model) is fam
    return fam.dims_of(model)


def test_counts_at_the_file_sizes():
    model = json.loads((HERE / "configs" / "nemotron-h-47b.json").read_text())
    d = _dims(model)
    assert (d.L, d.Lm, d.Lf, d.dI, d.C) == (1, 5, 5, 16384, 20480)
    # Mamba: in_proj 8192 x (16384 + 20480 + 256), out_proj 16384 x 8192;
    # MLP 2 x 8192 x 30720; attention q 8192x8192, k and v 8192x1024, o
    # 8192x8192; the 8192 x 32768 head
    mamba = 8192 * 37120 + 16384 * 8192
    assert fam.matmul_params(d) == (5 * mamba + 5 * 503_316_480
                                    + 150_994_944 + 268_435_456)
    # conv filters and biases, 20480 x 5, in every Mamba layer
    assert fam.weight_bytes(d) == 2 * (fam.matmul_params(d) + 5 * 102_400)
    # one attention layer over 101 keys: 4 x 64 heads x 128 x 101
    assert fam.attn_flops(d, 100) == 3_309_568
    # and the recurrence: 5 x 256 x 64 x 256 a state, 2 x 4 x 20480 conv
    assert fam.decode_flops(d, 100) == (2 * fam.matmul_params(d) + 3_309_568
                                        + 5 * (20_971_520 + 163_840))
    # a token at 100: 5 layers of state (256 x 64 x 256 f32, read and
    # written) and conv window (3 x 20480 bf16, read and written), and 7
    # pages of 16 x 8 KV heads x 128 x (K, V) x 2 bytes plus q and out
    assert fam.decode_bytes(d, 100, 16) == (5 * (33_554_432 + 245_760)
                                            + 458_752 + 32_768)


def _decode_record(modules, tokens_out=2):
    model = json.loads((HERE / "configs" / "nemotron-h-47b.json").read_text())
    # one request: prompt 100, 3 tokens out, the 2nd and 3rd decoded at
    # positions 100 and 101, at 0.2 s and 0.3 s of a 1 s window
    return {"model": model, "modules": modules, "decode_chunk": 8,
            "block_size": 16, "window_s": 1.0,
            "requests": [{"prompt": 100, "out": 3, "first": 0.1,
                          "finished": 0.3}],
            "counters": {"tokens_out": tokens_out},
            "peak": {"hbm_bytes_per_s": 819e9}}


def test_decode_hbm_roofline_on_a_hand_made_record():
    prog = fam.DECODE_PROGRAM
    rec = _decode_record([(prog, 0.0, 0.02, 1.0),
                          ("jit__prefill_window_impl", 0.1, 0.3, 1.0),
                          (prog, 0.9, 0.02, 0.5)])
    # 1.5 runs of 8 steps read the weights 12 times: 5 Mamba layers
    # (in_proj 8192 x 37120, out_proj 16384 x 8192, conv 20480 x 5), 5
    # MLPs (2 x 8192 x 30720), one attention layer (2 x 8192 x 8192 + 2 x
    # 8192 x 1024) and the 8192 x 32768 head, in bf16
    weights = 12 * 2 * (5 * (8192 * 37120 + 16384 * 8192 + 20480 * 5)
                        + 5 * 2 * 8192 * 30720
                        + 2 * 8192 * 8192 + 2 * 8192 * 1024 + 8192 * 32768)
    # each token: 5 layers of f32 state (256 x 64 x 256) and bf16 conv
    # window (3 x 20480), read and written; 7 KV pages (16 tokens, 8 heads
    # of 128, K and V, bf16) and q in, out out (64 x 128 bf16 each)
    state = 5 * (256 * 64 * 256 * 4 * 2 + 3 * 20480 * 2 * 2)
    kv = 7 * 16 * 8 * 128 * 2 * 2 + 2 * 64 * 128 * 2
    want = 100 * (weights + 2 * (state + kv)) / 819e9 / 0.04
    assert fam.decode_hbm_roofline(rec) == pytest.approx(want, rel=1e-12)
    note, = rec["notes"]
    assert note.startswith("decode_hbm_roofline: ") and "12 steps" in note
    # silent where the decode program did not run, or decoded nothing
    assert fam.decode_hbm_roofline(_decode_record(
        [("jit__prefill_window_impl", 0.1, 0.3, 1.0)])) is None
    assert fam.decode_hbm_roofline(_decode_record(
        [(prog, 0.0, 0.02, 1.0)], tokens_out=0)) is None


def test_program_departures_name_what_differs(tiny):
    conf, cfg, _ = tiny
    assert fam.program_departures(conf, cfg) == {}
    bad = dataclasses.replace(cfg, ssm_groups=1, pos_emb="rope")
    assert set(fam.program_departures(conf, bad)) == {"n_groups", "pos_emb"}
    with pytest.raises(ValueError, match="relu2"):
        fam.dims_of(dict(conf, mlp_hidden_act="silu"))


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    root = copy_checkout(tmp_path_factory.mktemp("checkout"))
    mix = {"loop": "closed", "clients": 4,
           "prompt": {"dist": "uniform", "lo": 8, "hi": 90},
           "output": {"dist": "uniform", "lo": 8, "hi": 24}, "block": 8}
    add_cell(root, "tinyhybrid", tiny_hybrid_config(), {"tinydoc": mix})
    return root


def test_smoke_hybrid_cell_runs_correct(hybrid_root):
    out = cellrun.run(hybrid_root, "tinyhybrid.tinydoc", SEED, 2,
                      trace=False, need_chip=False, log=lambda *_: None)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    gap = out["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_fp8_control_fails_the_limit(hybrid_root):
    out = cellrun.run(hybrid_root, "tinyhybrid.tinydoc", SEED, 1,
                      trace=False, need_chip=False, control=True,
                      log=lambda *_: None)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > 0.01
