"""A cell whose configuration names a tensor-parallel mesh, on the CPU:
the mesh is built over the cell's own devices, the weights are made on
their shards and equal the unsharded ones, and a whole smoke-size run on
four virtual devices comes out ``correct``. The run needs four devices
before JAX starts, so it runs in a child process."""
import json
import os
import subprocess
import sys

import pytest

import cellrun
from benchkit import REPO, TINY_MIXES, add_cell, copy_checkout, \
    tiny_config, tiny_mesh_config

SEED = 2 ** 31 + 777

MESH_SCRIPT = r"""
import json
import os
import sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("REPRO_MESH_MODEL", None)
root, bench, src, seed = sys.argv[1], sys.argv[2], sys.argv[3], int(sys.argv[4])
sys.path[:0] = [bench, src]
from pathlib import Path
import jax
import numpy as np
import cellrun
from repro.distributed.sharding import serve_param_shardings

root = Path(root)
cell = cellrun.load_cell(root, "tinymesh.tinyopen")
conf = cell["config"]
cfg = cellrun.build_config(conf)
devs = jax.devices()[:4]
ctx = cellrun.mesh_ctx(conf, devs)
assert dict(ctx.mesh.shape) == {"data": 1, "model": 4}, ctx.mesh.shape
assert list(ctx.mesh.devices.flat) == devs

# every leaf is born on the shards the engine serves it from, and holds
# the values of the unsharded init
params = cellrun.make_weights(cfg, seed, ctx)
want = serve_param_shardings(cfg, params, ctx)
leaves = jax.tree_util.tree_leaves_with_path(params)
wants = jax.tree_util.tree_leaves(want)
plain = jax.tree_util.tree_leaves(cellrun.make_weights(cfg, seed))
sharded = 0
for (path, leaf), sh, ref in zip(leaves, wants, plain):
    name = jax.tree_util.keystr(path)
    assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), name
    assert np.array_equal(np.asarray(leaf), np.asarray(ref)), name
    shapes = {s.data.shape for s in leaf.addressable_shards}
    if sh.spec and sh.spec[-1] == "model":
        assert shapes == {leaf.shape[:-1] + (leaf.shape[-1] // 4,)}, name
        sharded += 1
    else:
        assert shapes == {leaf.shape}, name
assert sharded == 7, sharded
del params, plain

out = cellrun.run(root, "tinymesh.tinyopen", seed, 2, trace=False,
                  need_chip=False, log=lambda *_: None)

# a mesh that does not span the cell's chips is refused before any work
try:
    cellrun.run(root, "tinymesh2.tinyopen", seed, 2, trace=False,
                need_chip=False, log=lambda *_: None)
    refused = None
except cellrun.CellError as e:
    refused = str(e)

# the exchange between chips left out: each chip takes its own shard of
# an activation in place of the others' (the shapes stay as they were)
import jax.numpy as jnp
from repro.distributed import sharding
from repro.models import attention, mlp


def no_exchange(x, axis=-1):
    c = sharding.current_ctx()
    if c is None or c.mesh is None or not c.manual or c.tp is None:
        return x
    return jnp.concatenate([x] * c.mesh.shape[c.tp], axis=axis % x.ndim)


attention.gather_tp = mlp.gather_tp = no_exchange
jax.clear_caches()
bad = cellrun.run(root, "tinymesh.tinyopen", seed, 2, trace=False,
                  need_chip=False, log=lambda *_: None)
print(json.dumps({"correct": out["correct"], "attempted": out["attempted"],
                  "failed": out["failed"], "count": out["device"]["count"],
                  "checks": out["checks"], "refused": refused,
                  "no_exchange": {"correct": bad["correct"],
                                  "checks": bad["checks"]}}))
"""


def test_mesh_is_none_without_a_mesh_entry():
    assert cellrun.mesh_ctx(tiny_config(), [object()]) is None


@pytest.mark.parametrize("mesh,devs,match", [
    ({"data": 1, "model": 4}, 1, "spans 4 devices; the cell has 1 chips"),
    ({"data": 1, "model": 2}, 4, "spans 2 devices; the cell has 4 chips"),
    ({"model": 4}, 4, "give its data and model axes"),
])
def test_mesh_must_span_the_cell_chips(mesh, devs, match):
    conf = dict(tiny_config(), mesh=mesh)
    with pytest.raises(cellrun.CellError, match=match):
        cellrun.mesh_ctx(conf, [object()] * devs)


def test_mesh_cell_runs_correct_on_four_devices(tmp_path):
    """A sound run is correct; the same run with the exchange between
    chips left out is not; a mesh of two on a four-chip cell is refused."""
    root = copy_checkout(tmp_path)
    add_cell(root, "tinymesh", tiny_mesh_config(),
             {"tinyopen": TINY_MIXES["tinyopen"]}, chips=4)
    bad = dict(tiny_mesh_config(), mesh={"data": 1, "model": 2})
    add_cell(root, "tinymesh2", bad, {"tinyopen": TINY_MIXES["tinyopen"]},
             chips=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT, str(root),
         str(root / "benchmarks" / "chip"), str(REPO / "src"), str(SEED)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["count"] == 4
    assert "spans 2 devices; the cell has 4 chips" in out["refused"]
    bad = out["no_exchange"]
    assert bad["correct"] is False
    gap = bad["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
