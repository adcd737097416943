"""Sharding rules + a REAL multi-device lower/compile in a subprocess
(the main test process keeps 1 device; the subprocess gets 8 virtual
devices via XLA_FLAGS, mirroring the dry-run mechanics on a small mesh)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import ShardCtx, constrain, param_specs
from repro.models import lm


def test_constrain_noop_without_mesh():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    assert constrain(x, "dp", None) is x


def test_param_specs_rules():
    cfg = get_config("qwen3-14b").smoke()
    shapes = jax.eval_shape(lambda k: lm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    ctx = ShardCtx(mesh=None)
    specs = param_specs(shapes, ctx)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    by_path = {"/".join(str(getattr(k, "key", k)) for k in path): spec
               for path, spec in flat}
    # stacked blocks get a leading None for the layer dim
    wq = [v for k, v in by_path.items() if k.endswith("wq")][0]
    assert wq[0] is None and len(wq) == 3
    embed = [v for k, v in by_path.items() if k.endswith("embed")][0]
    assert len(embed) == 2


def test_divisibility_guard_drops_axis():
    """vocab 503 (smoke) is not divisible by any axis -> embed spec has no
    mesh axes on dim 0 unless padded_vocab divides."""
    cfg = get_config("stablelm-1.6b").smoke()
    assert cfg.padded_vocab % 256 == 0


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import dataclasses
from repro.configs import get_config, SHAPES_BY_NAME
from repro.launch.mesh import make_ctx, small_mesh
from repro.train.train_step import train_input_specs, make_decode_step
mesh = small_mesh(data=2, model=4)
ctx = make_ctx(mesh)
cfg = dataclasses.replace(get_config("stablelm-1.6b").smoke(),
                          d_model=128, vocab_size=1024, num_heads=8,
                          num_kv_heads=4, head_dim=16, d_ff=256)
shape = dataclasses.replace(SHAPES_BY_NAME["train_4k"], seq_len=64,
                            global_batch=8)
step, specs, _ = train_input_specs(cfg, ctx, shape)
with mesh:
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*specs).compile()
    mem = compiled.memory_analysis()
dshape = dataclasses.replace(SHAPES_BY_NAME["decode_32k"], seq_len=64,
                             global_batch=8)
dstep, dspecs, _ = make_decode_step(cfg, ctx, dshape)
with mesh:
    dcomp = jax.jit(dstep, donate_argnums=(1,)).lower(*dspecs).compile()
print(json.dumps({"train_temp": mem.temp_size_in_bytes,
                  "decode_ok": True}))
"""


@pytest.mark.slow
def test_multidevice_lower_compile_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["decode_ok"] and out["train_temp"] > 0
