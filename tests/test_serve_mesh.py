"""Tensor-parallel sharded serving (docs/sharded_serving.md).

Three layers of defence:

* in-process unit tests for the mesh-divisibility gates and the typed
  refusal — no devices needed;
* a subprocess with 4 virtual CPU devices asserting the spec rules on a
  REAL mesh plus the no-accidental-gather invariant on the lowered
  decode-chunk HLO (zero all-reduces; every all-gather far below the
  per-device pool shard — the pool must never be reassembled);
* a subprocess running the full bit-exactness matrix: 2- and 4-way
  meshes x sync/async decode x prefix cache on/off, with chunked prefill
  and block growth exercised, against the single-device oracle.
"""
import dataclasses
import os
import subprocess
import sys

import pytest

from repro.configs import get_config
from repro.distributed.sharding import (MeshDivisibilityError,
                                        serve_attn_sharded,
                                        serve_mlp_sharded,
                                        validate_serve_mesh)


def _smoke():
    return get_config("stablelm-1.6b").smoke()


def test_serve_attn_sharded_gates():
    cfg = _smoke()  # KV=2, H=4, d_model=64
    assert serve_attn_sharded(cfg, 2)
    assert not serve_attn_sharded(cfg, 4)      # 4 does not divide KV=2
    assert not serve_attn_sharded(cfg, 1)      # single device: no TP
    ssm = dataclasses.replace(cfg, ssm=True)
    assert not serve_attn_sharded(ssm, 2)      # SSM serves replicated
    wide = dataclasses.replace(cfg, num_heads=8, num_kv_heads=4)
    assert serve_attn_sharded(wide, 4)


def test_serve_mlp_sharded_gates():
    cfg = _smoke()  # d_ff=96, d_model=64
    assert serve_mlp_sharded(cfg, 2)
    assert not serve_mlp_sharded(cfg, 64)      # 64 ∤ d_ff=96
    assert not serve_mlp_sharded(dataclasses.replace(cfg, ssm=True), 2)


def test_validate_serve_mesh_typed_error():
    cfg = _smoke()
    validate_serve_mesh(cfg, 1)                # trivial axis: fine
    validate_serve_mesh(cfg, 2)                # divides: fine
    with pytest.raises(MeshDivisibilityError) as ei:
        validate_serve_mesh(cfg, 4)
    assert "num_kv_heads=2" in str(ei.value)
    # typed subclass of ValueError so callers can catch broadly
    assert isinstance(ei.value, ValueError)
    # SSM/hybrid architectures serve replicated on any axis size
    validate_serve_mesh(dataclasses.replace(cfg, ssm=True), 4)


# ---------------------------------------------------------------- subprocess
# spec rules on a real 4-way mesh + the no-accidental-gather HLO invariant
SPECS_HLO_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("REPRO_MESH_MODEL", None)
import dataclasses
import json
import jax
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.distributed.hlo_analysis import analyze_hlo
from repro.distributed.sharding import (serve_param_specs, serve_pool_spec,
                                        serve_kv_cache_spec)
from repro.launch.mesh import make_ctx, small_mesh
from repro.models import lm
from repro.serve.engine import ServeEngine

cfg = dataclasses.replace(get_config("stablelm-1.6b").smoke(),
                          num_heads=8, num_kv_heads=4)
ctx = make_ctx(small_mesh(data=1, model=4))

# ---- spec rules: projections shard their LAST dim; everything else
# (embed, lm_head, norms) is replicated so per-shard compute is bit-exact
shapes = jax.eval_shape(lambda k: lm.init_params(cfg, k),
                        jax.random.PRNGKey(0))
specs = serve_param_specs(cfg, shapes, ctx)
flat = jax.tree_util.tree_flatten_with_path(
    specs, is_leaf=lambda x: isinstance(x, P))[0]
by_path = {"/".join(str(getattr(k, "key", k)) for k in path): spec
           for path, spec in flat}
for name in ("wq", "wk", "wv", "wo", "wi", "wg", "wd"):
    spec = [v for k, v in by_path.items()
            if k.startswith("blocks") and k.endswith(name)][0]
    assert spec[-1] == "model" and all(s is None for s in spec[:-1]), \
        (name, spec)
for name in ("embed", "lm_head"):
    spec = [v for k, v in by_path.items() if k.endswith(name)][0]
    assert all(s is None for s in spec), (name, spec)
assert serve_pool_spec(cfg, ctx) == P(None, None, None, "model", None,
                                      None)
assert serve_kv_cache_spec(cfg, ctx) == P(None, None, "model", None, None)

# ---- lowered decode-chunk HLO: zero all-reduces, and no all-gather whose
# single largest operand/result approaches the per-device pool shard (the
# pool is (N, L, 2, R/4, bs, W) per device and must NEVER be gathered)
params = lm.init_params(cfg, jax.random.PRNGKey(0))
eng = ServeEngine(cfg, params, ctx=ctx, decode_chunk=4, max_batch=4,
                  kv_blocks=48, block_size=8, max_admit=2)
shard_bytes = eng._pkv.addressable_shards[0].data.nbytes
hlo = eng._decode_paged.lower(eng.params, eng._pkv, eng._tables_dev,
                              *eng._carry, n=4).compile().as_text()
cost = analyze_hlo(hlo)
eng.close()
assert cost.collective_counts["all-reduce"] == 0, cost.collective_counts
assert cost.collective_counts["all-gather"] > 0, \
    "TP decode must reassemble activations via all-gather"
biggest = cost.collective_max_bytes["all-gather"]
assert biggest < shard_bytes / 2, (biggest, shard_bytes)
print(json.dumps({"ok": True, "pool_shard_bytes": int(shard_bytes),
                  "ag_count": cost.collective_counts["all-gather"],
                  "ag_max_bytes": biggest}))
"""

# full parity matrix vs the single-device oracle
PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("REPRO_MESH_MODEL", None)
import dataclasses
import jax
import numpy as np
from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import ServeEngine
from repro.launch.mesh import make_ctx, small_mesh

cfg = dataclasses.replace(get_config("stablelm-1.6b").smoke(),
                          num_heads=8, num_kv_heads=4)
params = lm.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
# lengths straddle the prefill window (16) and block size (8): 41 streams
# across multiple chunked-prefill windows, the short ones grow blocks
prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
           for n in (23, 5, 9, 41, 17, 21)]
# the last two share prompt 0's first two blocks (16 tokens); with only 4
# seats they are admitted after it retires and registers its prefix, so
# prefix runs exercise real cache hits + CoW forks
prompts[4] = np.concatenate([prompts[0][:16], prompts[4][16:]])
prompts[5] = np.concatenate([prompts[0][:16], prompts[5][16:]])
kw = dict(decode_chunk=4, max_batch=4, kv_blocks=48, block_size=8,
          max_admit=2)

def run(ctx=None, async_decode=False, prefix=False):
    with ServeEngine(cfg, params, ctx=ctx, async_decode=async_decode,
                     prefix_cache=prefix, **kw) as eng:
        outs = eng.generate(prompts, max_new=12)
        stats = dict(eng.stats)
    return outs, stats

base, bstats = run()
assert bstats["grown_blocks"] > 0 and bstats["prefill_windows"] > 0, bstats
for mp in (2, 4):
    ctx = make_ctx(small_mesh(data=1, model=mp))
    for async_decode in (False, True):
        for prefix in (False, True):
            outs, st = run(ctx, async_decode, prefix)
            for i, (a, b) in enumerate(zip(base, outs)):
                assert np.array_equal(a, b), \
                    (mp, async_decode, prefix, i, a.tolist(), b.tolist())
            if prefix:
                assert st["prefix_hits"] > 0, (mp, async_decode, st)
            print(f"mp={mp} async={async_decode} prefix={prefix}: exact")

# per-device pool footprint shrinks by the mesh factor
ctx = make_ctx(small_mesh(data=1, model=4))
eng = ServeEngine(cfg, params, ctx=ctx, **kw)
full = eng._pkv.nbytes
shard = eng._pkv.addressable_shards[0].data.nbytes
assert shard * 4 == full, (full, shard)
eng.close()

# env-driven mesh: REPRO_MESH_MODEL clamps to the largest usable divisor
os.environ["REPRO_MESH_MODEL"] = "4"
cfg2 = get_config("stablelm-1.6b").smoke()   # KV=2: 4 clamps to 2
params2 = lm.init_params(cfg2, jax.random.PRNGKey(0))
eng = ServeEngine(cfg2, params2, **kw)
assert eng._tp == 2, eng._tp
eng.close()
del os.environ["REPRO_MESH_MODEL"]

# an EXPLICIT indivisible mesh is refused with the typed error
from repro.distributed.sharding import MeshDivisibilityError
try:
    ServeEngine(cfg2, params2, ctx=make_ctx(small_mesh(data=1, model=4)),
                **kw)
    raise AssertionError("expected MeshDivisibilityError")
except MeshDivisibilityError:
    pass
print("PARITY OK")
"""


def _run_sub(script: str, timeout: float):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_sharded_decode_hlo_has_no_pool_gather():
    r = _run_sub(SPECS_HLO_SCRIPT, 600)
    assert r.returncode == 0, r.stderr[-3000:]
    import json
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["ag_max_bytes"] < out["pool_shard_bytes"] / 2


@pytest.mark.slow
def test_mesh_serving_bit_exact_vs_single_device():
    r = _run_sub(PARITY_SCRIPT, 900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == "PARITY OK"
