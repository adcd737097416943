"""Gather-free paged decode-attention kernels vs. the gather oracle.

The Pallas kernel (interpret mode) and the traced-bound XLA page loop must
reproduce the materialize-then-mask reference (``kvcache.gather_pages`` +
masked softmax) across GQA ratios, ragged per-row lengths, rows parked on
the sink block, and block sizes that do not divide ``pos + 1``. Also the
causal block-pruning parity for the prefill flash kernel: skipping
fully-above-diagonal kv blocks is bit-identical to masking them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.ops import flash_attention, paged_attention
from repro.kernels.paged_attention import kv_pack
from repro.models.attention import init_attention, paged_decode_attention
from repro.serve.kvcache import gather_read_attention as _gather_oracle

KEY = jax.random.PRNGKey(0)


def _make_case(B, H, KV, hd, bs, mb, lengths, seed=0, dtype=jnp.float32,
               L=3):
    """Random ``L``-layer pool (KV heads packed into lane rows as
    ``init_kv_pool`` packs them) + disjoint per-row block tables covering
    ``lengths``.

    Rows with length < 0 are left entirely on the sink block (the engine's
    inactive-slot state); their length is clamped to 0 for the mask.
    """
    rng = np.random.default_rng(seed)
    N = B * mb + 1
    ks = jax.random.split(KEY, 2)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    P = kv_pack(KV, hd)
    pool_kv = jax.random.normal(ks[1], (N, L, 2, KV // P, bs, P * hd), dtype)
    tables = np.zeros((B, mb), np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for b in range(B):
        if lengths[b] < 0:
            continue                       # sink-parked row
        nb = lengths[b] // bs + 1
        for j in range(nb):
            tables[b, j] = free.pop()
    lengths = np.maximum(np.asarray(lengths, np.int32), 0)
    return q, pool_kv, jnp.asarray(tables), jnp.asarray(lengths)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 1)])
def test_paged_matches_gather_across_gqa_and_ragged_lengths(impl, H, KV):
    B, hd, bs, mb = 5, 32, 16, 6
    # ragged: empty row (pos=0), mid-block, exact block boundary (bs does
    # not divide pos+1 except row 3), near-capacity
    lengths = [0, 7, bs - 1, 2 * bs, mb * bs - 1]
    q, pool_kv, tables, ln = _make_case(B, H, KV, hd, bs, mb, lengths)
    for layer in range(pool_kv.shape[0]):
        out = paged_attention(q, pool_kv, tables, ln, layer, impl=impl)
        ref = _gather_oracle(q, pool_kv, layer, tables, ln)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_paged_inactive_sink_rows(impl):
    """Rows parked on the sink block (every table entry 0) stay finite and
    match the oracle; live rows are untouched by their presence."""
    B, H, KV, hd, bs, mb = 4, 4, 2, 16, 8, 4
    lengths = [5, -1, 20, -1]              # rows 1 and 3 are sink-parked
    q, pool_kv, tables, ln = _make_case(B, H, KV, hd, bs, mb, lengths)
    assert int(tables[1].sum()) == 0 and int(tables[3].sum()) == 0
    out = paged_attention(q, pool_kv, tables, ln, 1, impl=impl)
    ref = _gather_oracle(q, pool_kv, 1, tables, ln)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("bs,pos", [(4, 4), (4, 10), (3, 7), (5, 5)])
def test_paged_block_size_not_dividing_pos(impl, bs, pos):
    B, H, KV, hd, mb = 2, 4, 2, 16, 4
    q, pool_kv, tables, ln = _make_case(B, H, KV, hd, bs, mb, [pos, pos % bs])
    out = paged_attention(q, pool_kv, tables, ln, 2, impl=impl)
    ref = _gather_oracle(q, pool_kv, 2, tables, ln)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_paged_decode_attention_impl_switch_parity(impl):
    """Full module-level op (projection + fused append + read + output
    proj): the gather-free impls match the gather oracle, and the fused
    K/V append leaves identical pool contents."""
    cfg = get_config("stablelm-1.6b").smoke()
    B, mb, bs, N = 3, 4, 4, 16
    p = init_attention(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, 1, cfg.d_model))
    P = kv_pack(cfg.num_kv_heads, cfg.hd)
    pool_kv = jax.random.normal(
        jax.random.PRNGKey(3),
        (N, 2, 2, cfg.num_kv_heads // P, bs, P * cfg.hd))
    rng = np.random.default_rng(0)
    perm = rng.permutation(np.arange(1, N))[:B * mb].reshape(B, mb)
    tables = jnp.asarray(perm.astype(np.int32))
    pos = jnp.asarray([0, 5, 11], jnp.int32)
    active = jnp.asarray([True, True, False])
    y_ref, pool_ref = paged_decode_attention(p, x, cfg, pool_kv, 1, tables,
                                             pos, active, impl="gather")
    y, pool = paged_decode_attention(p, x, cfg, pool_kv, 1, tables,
                                     pos, active, impl=impl)
    np.testing.assert_array_equal(np.asarray(pool), np.asarray(pool_ref))
    act = np.asarray(active)
    diff = np.abs(np.asarray(y, np.float32) - np.asarray(y_ref, np.float32))
    assert diff[act].max() < 2e-2      # bf16 compute dtype tolerance
    assert np.isfinite(np.asarray(y, np.float32)).all()


@pytest.mark.parametrize("S,block", [(128, 64), (256, 64), (192, 32)])
def test_flash_causal_prune_bit_identical(S, block):
    """Skipping fully-above-diagonal kv blocks (compute + fetch) is
    bit-identical to masking them to NEG_INF."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, S, 4, 32))
    k = jax.random.normal(ks[1], (2, S, 2, 32))
    v = jax.random.normal(ks[2], (2, S, 2, 32))
    pruned = flash_attention(q, k, v, causal=True, block_q=block,
                             block_k=block, prune=True)
    masked = flash_attention(q, k, v, causal=True, block_q=block,
                             block_k=block, prune=False)
    np.testing.assert_array_equal(np.asarray(pruned), np.asarray(masked))


def test_flash_non_causal_ignores_prune_flag():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32))
    k = jax.random.normal(ks[1], (1, 128, 4, 32))
    v = jax.random.normal(ks[2], (1, 128, 4, 32))
    a = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                        prune=True)
    b = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                        prune=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
