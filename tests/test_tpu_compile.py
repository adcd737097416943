"""Compile the serving path for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX and compiles for a chip described by
``topologies.get_topology_desc``; nothing runs. These tests hold the chip's
own verdicts on the main path at published widths:

* the Pallas paged decode kernel lowers through Mosaic (``interpret=False``)
  at stablelm-1.6b's widths and at two GQA configs' (mistral-nemo-12b's is
  a chip benchmark cell's);
* in the decode chunk the kernel's custom call keeps the HLO name
  ``paged_attention``, which the chip benchmark's trace reader
  (``benchmarks/chip/tracereduce.op_name``) matches;
* the decode chunk and the chunked-prefill window, with bf16 weights and a
  donated 4 GiB pool, update the pool in place: temporaries stay under
  1 GiB and arguments plus temporaries fit the chip's 15.75 GiB.

Shapes come from ``jax.eval_shape``; no array is ever placed on the
described device. The topology is described in a fixture, never at import,
so every test worker collects the same tests and only the worker given this
file loads the TPU library.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.paged_attention import kv_pack, paged_attention
from repro.models import lm
from repro.serve.kvcache import init_kv_pool

GiB = 1 << 30
#: v5e HBM the compiler may plan into (16 GB chip)
HBM_BYTES = 15.75 * GiB
#: 1,366 blocks x 16 tokens x 3 MiB per block at stablelm widths = 4 GiB
POOL_BLOCKS, BLOCK = 1366, 16
B = 8
#: block-table width: prompts of up to 2048 tokens plus 128 new ones
MAX_BLOCKS = (2048 + 128) // BLOCK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip can be written to the persistent
    cache but never read back without the chip: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


@pytest.fixture(scope="module")
def stablelm(one_chip):
    cfg = dataclasses.replace(get_config("stablelm-1.6b"),
                              param_dtype="bfloat16")
    params = _spec(jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    pool = _spec(jax.eval_shape(
        lambda: init_kv_pool(cfg, POOL_BLOCKS, BLOCK)), one_chip)
    assert pool.size * pool.dtype.itemsize >= 4 * GiB
    return cfg, params, pool


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "qwen3-14b",
                                  "mistral-nemo-12b"])
def test_paged_kernel_lowers_through_mosaic(arch, one_chip,
                                            no_persistent_cache):
    cfg = get_config(arch)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    P = kv_pack(KV, hd)
    q = jax.ShapeDtypeStruct((B, H, hd), jnp.bfloat16, sharding=one_chip)
    pool = jax.ShapeDtypeStruct(
        (256, cfg.num_layers, 2, KV // P, BLOCK, P * hd), jnp.bfloat16,
        sharding=one_chip)
    fn = jax.jit(lambda q, pool, t, ln, layer: paged_attention(
        q, pool, t, ln, layer, impl="pallas", interpret=False))
    compiled = fn.lower(q, pool, _i32(one_chip, B, MAX_BLOCKS),
                        _i32(one_chip, B), _i32(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _assert_in_place(compiled):
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < GiB, \
        f"temporaries {m.temp_size_in_bytes / GiB:.3f} GiB: a pool copy?"
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM_BYTES


@pytest.fixture(scope="module")
def decode_chunk(stablelm, one_chip, no_persistent_cache):
    """The 8-step decode chunk at stablelm's widths, compiled with the
    Mosaic kernel (the program asks the platform which kernel mode to use;
    here the platform is the CPU, so it is told the chip is there)."""
    cfg, params, pool = stablelm
    fn = jax.jit(lambda p, pkv, t, ln, last, rem: lm.decode_chunk_paged(
        cfg, p, pkv, t, (ln, last, rem), 8, impl="pallas"),
        donate_argnums=(1,))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_tpu", lambda: True)
        return fn.lower(params, pool, _i32(one_chip, B, MAX_BLOCKS),
                        _i32(one_chip, B), _i32(one_chip, B),
                        _i32(one_chip, B)).compile()


def test_decode_chunk_updates_pool_in_place(decode_chunk):
    assert "tpu_custom_call" in decode_chunk.as_text()
    _assert_in_place(decode_chunk)


def _trace_op_name():
    """``op_name`` of the chip benchmark's trace reader, loaded by path."""
    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "tracereduce.py")
    spec = importlib.util.spec_from_file_location("_tracereduce", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.op_name


def test_decode_chunk_kernel_keeps_its_trace_name(decode_chunk):
    """``paged_decode_roofline`` sums the device time of ops named
    ``paged_attention``: the kernel's custom call must keep that name."""
    op_name = _trace_op_name()
    names = {op_name(line.strip()) for line in
             decode_chunk.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    assert "paged_attention" in names, names


def test_prefill_window_updates_pool_in_place(stablelm, one_chip,
                                              no_persistent_cache):
    cfg, params, pool = stablelm
    C = 128
    fn = jax.jit(lambda p, pkv, t, tk, st, vd, li: lm.prefill_window_paged(
        cfg, p, pkv, t, tk, st, vd, li), donate_argnums=(1,))
    compiled = fn.lower(
        params, pool, _i32(one_chip, B, MAX_BLOCKS), _i32(one_chip, B, C),
        _i32(one_chip, B),
        jax.ShapeDtypeStruct((B, C), jnp.bool_, sharding=one_chip),
        _i32(one_chip, B)).compile()
    _assert_in_place(compiled)
