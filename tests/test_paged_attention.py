"""Gather-free paged decode-attention kernels vs. the gather oracle.

The Pallas kernel (interpret mode) and the traced-bound XLA page loop must
reproduce the materialize-then-mask reference (``kvcache.gather_pages`` +
masked softmax) across GQA ratios, ragged per-row lengths, rows parked on
the sink block, and block sizes that do not divide ``pos + 1``. The kernel
fetches a row's pages in groups; ``pallas/K`` runs it with ``K`` pages a
group, so lengths land on and around group edges, rows span several
groups and tables end inside a group. A structural guard keeps its grid
at one step per row. Also the
causal block-pruning parity for the prefill flash kernel: skipping
fully-above-diagonal kv blocks is bit-identical to masking them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.ops import flash_attention, paged_attention
from repro.kernels.paged_attention import _paged_attention_pallas, kv_pack
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


_grouped = jax.jit(_paged_attention_pallas,
                   static_argnames=("interpret", "group"))


def _attend(impl, q, pool_kv, tables, lengths, layer):
    """``pallas`` and ``xla`` as the engine calls them; ``pallas/K`` is
    the kernel in interpret mode with ``K`` pages a DMA group (its own
    choice, from the shapes, fits a whole small table in one group)."""
    if impl.startswith("pallas/"):
        return _grouped(q, pool_kv, tables, lengths, layer, interpret=True,
                        group=int(impl.split("/")[1]))
    return paged_attention(q, pool_kv, tables, lengths, layer, impl=impl)


@pytest.mark.parametrize("impl", ["pallas", "xla", "pallas/2", "pallas/4"])
@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 1)])
def test_paged_matches_gather_across_gqa_and_ragged_lengths(impl, H, KV):
    B, hd, bs, mb = 5, 32, 16, 6
    # ragged: empty row (pos=0), mid-block, exact block boundary (bs does
    # not divide pos+1 except row 3), near-capacity. With 2 pages a group
    # row 3 opens a second group with one page and row 4 fills three; with
    # 4, the 6-column table ends inside the second group
    lengths = [0, 7, bs - 1, 2 * bs, mb * bs - 1]
    q, pool_kv, tables, ln = _make_case(B, H, KV, hd, bs, mb, lengths)
    for layer in range(pool_kv.shape[1]):
        out = _attend(impl, q, pool_kv, tables, ln, layer)
        ref = _gather_oracle(q, pool_kv, layer, tables, ln)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5


@pytest.mark.parametrize("impl", ["pallas", "xla", "pallas/2"])
def test_paged_inactive_sink_rows(impl):
    """Rows parked on the sink block (every table entry 0) stay finite and
    match the oracle; live rows are untouched by their presence."""
    B, H, KV, hd, bs, mb = 4, 4, 2, 16, 8, 4
    lengths = [5, -1, 20, -1]              # rows 1 and 3 are sink-parked
    q, pool_kv, tables, ln = _make_case(B, H, KV, hd, bs, mb, lengths)
    assert int(tables[1].sum()) == 0 and int(tables[3].sum()) == 0
    out = _attend(impl, q, pool_kv, tables, ln, 1)
    ref = _gather_oracle(q, pool_kv, 1, tables, ln)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


@pytest.mark.parametrize("impl", ["pallas", "xla", "pallas/3"])
@pytest.mark.parametrize("bs,pos", [(4, 4), (4, 10), (3, 7), (5, 5)])
def test_paged_block_size_not_dividing_pos(impl, bs, pos):
    B, H, KV, hd, mb = 2, 4, 2, 16, 4
    q, pool_kv, tables, ln = _make_case(B, H, KV, hd, bs, mb, [pos, pos % bs])
    out = _attend(impl, q, pool_kv, tables, ln, 2)
    ref = _gather_oracle(q, pool_kv, 2, tables, ln)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


#: the chip cells' packings: MHA at hd 64, two heads a row (P = 2, G = 1);
#: GQA at hd 128, one head a row, four queries a KV head (P = 1, G = 4)
PACKINGS = {"mha-hd64-P2": (4, 4, 64), "gqa-hd128-G4": (8, 2, 128)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("packing", sorted(PACKINGS))
@pytest.mark.parametrize("K", [2, 3])
@pytest.mark.parametrize("rows", ["edges", "one-row"])
def test_paged_group_edges(rows, K, packing, dtype):
    """Lengths ending just before, on and just after a group edge, a row
    over four groups, a sink-parked row and an empty one, in a table of
    ``3K + 1`` columns (not a multiple of ``K``); or a single row filling
    the whole table. Against the oracle in float32 on the same values."""
    H, KV, hd = PACKINGS[packing]
    bs, mb = 8, 3 * K + 1
    lengths = {"edges": [K * bs - 1, K * bs, K * bs + 1, mb * bs - 1, -1, 0],
               "one-row": [mb * bs - 1]}[rows]
    q, pool_kv, tables, ln = _make_case(len(lengths), H, KV, hd, bs, mb,
                                        lengths, dtype=jnp.dtype(dtype), L=2)
    assert kv_pack(KV, hd) == (2 if hd == 64 else 1)
    out = _attend(f"pallas/{K}", q, pool_kv, tables, ln, 1)
    assert out.dtype == q.dtype
    ref = _gather_oracle(q.astype(jnp.float32), pool_kv.astype(jnp.float32),
                         1, tables, ln)
    tol = 2e-5 if dtype == "float32" else 2.0 ** -8 * float(
        jnp.max(jnp.abs(ref)))
    assert jnp.max(jnp.abs(out.astype(jnp.float32) - ref)) < tol


def _pallas_grids(jaxpr):
    """Grids of every ``pallas_call`` in ``jaxpr``, nested ones included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += _pallas_grids(sub)
    return grids


@pytest.mark.parametrize("mb", [8, 64])
def test_paged_kernel_grid_is_one_step_per_row(mb):
    """The grid is ``(B,)`` whatever the table width and the number of
    packed KV rows: dead pages get no grid step."""
    B, H, KV, hd, bs = 3, 8, 8, 64, 16
    q, pool_kv, tables, ln = _make_case(B, H, KV, hd, bs, mb, [5, 40, 100],
                                        L=1)
    jaxpr = jax.make_jaxpr(lambda *a: paged_attention(*a, impl="pallas"))(
        q, pool_kv, tables, ln, 0)
    assert _pallas_grids(jaxpr.jaxpr) == [(B,)]


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
