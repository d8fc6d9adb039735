"""Sharded quantized serving: shard_map EP bit-parity vs the single-device
oracle, per-host sharded artifacts, and the mesh-aware engine.

The multi-device cells run in a subprocess: XLA_FLAGS must force the host
platform device count before jax initializes, which cannot happen inside
this process (same pattern as test_dryrun.py).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(script: str, timeout: int = 420, devices: int = 4):
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(REPO, "src"),
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
    )
    return subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


EP_PARITY_SCRIPT = r"""
import glob, json, os, tempfile
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import QuantConfig
from repro.core.quantizer import QTensor
from repro.launch.mesh import parse_mesh_spec
from repro.models import build_model, load_servable, quantize_and_plan, save_servable
from repro.parallel import sharding as rules
from repro.serving import Request, ServingEngine

assert jax.device_count() == 4, jax.device_count()

qc = QuantConfig(w_bits=2, group_size=16, mode="ptq", backend="pallas_ep")
cfg = configs.get_smoke("grok-1-314b", qc)  # MoE family: 4 experts
api = build_model(cfg)
params = api.init(jax.random.PRNGKey(0))
qparams, plan, qapi = quantize_and_plan(api, params)
mesh = parse_mesh_spec("dp=2,ep=2")

# ---- per-host sharded artifact: payload.shard{k} + per-shard sha256 ------
d = tempfile.mkdtemp()
step = save_servable(d, qapi, qparams, plan, mesh=mesh)
shard_files = [f for f in os.listdir(step) if ".shard" in f]
assert shard_files, "expected per-host shard files on disk"
man = json.load(open(os.path.join(step, "manifest.json")))
n_sharded = 0
for node in man["nodes"].values():
    for meta in node["arrays"].values():
        if "shards" in meta:
            n_sharded += 1
            assert all("sha256" in s and "index" in s for s in meta["shards"])
            assert "shape" in meta and "dtype" in meta
assert n_sharded > 0, "no payload used the sharded layout"

# ---- bit parity: sharded EP decode vs the single-device oracle -----------
def run_engine(mesh):
    eng = ServingEngine.from_artifact(d, n_slots=2, max_len=16, mesh=mesh)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=[1 + i, 2, 3], max_new_tokens=4))
    return {r.uid: r.output for r in eng.run()}

# sharded engine FIRST: each engine scopes the ambient activation mesh to
# its own dispatches, so a meshed engine must not leak its mesh into a
# mesh-less oracle built afterwards in the same process
sharded = run_engine(mesh)
oracle = run_engine(None)
assert oracle == sharded, f"tokens diverged: {oracle} vs {sharded}"
assert all(len(v) == 4 for v in oracle.values())

# ---- the loaded tree is on-mesh and expert sites go through shard_map ----
api2, qp2, art = load_servable(d, mesh=mesh)
packed_specs = [
    l.packed.sharding.spec for l in jax.tree.leaves(
        qp2, is_leaf=lambda x: isinstance(x, QTensor)
    ) if isinstance(l, QTensor)
]
assert any(
    any(ax is not None for ax in spec) for spec in packed_specs
), f"no QTensor payload actually sharded: {packed_specs}"

rules.set_activation_mesh(mesh)
cache_shapes = jax.eval_shape(lambda: api2.init_cache(2, 16))
cache = jax.device_put(
    api2.init_cache(2, 16), rules.cache_shardings(cache_shapes, mesh)
)
tok = jnp.zeros((2, 1), jnp.int32)
pos = jnp.zeros((2,), jnp.int32)
jaxpr = str(jax.make_jaxpr(
    lambda p, t, po, c: api2.decode(p, t, po, c)[0]
)(qp2, tok, pos, cache))
assert "shard_map" in jaxpr, "expert FFN did not lower through shard_map"
assert "all_to_all" in jaxpr, "no in-body dispatch/combine all-to-alls"
rules.set_activation_mesh(None)

# ---- a corrupt shard file fails closed (no silent partial restore) -------
bad = sorted(glob.glob(os.path.join(step, "*.shard0.npy")))[0]
with open(bad, "wb") as fh:
    fh.write(b"junk")
from repro.quant import load_artifact
try:
    load_artifact(d)
    raise SystemExit("corrupt shard restored as intact")
except IOError:
    pass
print("EP_PARITY_OK")
"""


def test_sharded_ep_decode_bit_parity_2x2_mesh():
    """Forced 4-device CPU mesh: per-host sharded artifact cold-start, EP
    decode bit-identical to the single-device artifact path, shard_map +
    all-to-alls in the decode jaxpr, corrupt shards fail closed."""
    r = _run_py(EP_PARITY_SCRIPT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "EP_PARITY_OK" in r.stdout


SHARDED_RESTORE_SCRIPT = r"""
import os, tempfile
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import parse_mesh_spec
from repro.parallel import sharding as rules
from repro.quant import load_artifact, quantize_weights, save_artifact

mesh = parse_mesh_spec("dp=2,ep=2")
tree = {
    "blocks": {"attn": {"wq": {"w": quantize_weights(
        jax.random.normal(jax.random.PRNGKey(0), (64, 128)), 2, 16
    )}}},
    "embed": {"table": jax.random.normal(jax.random.PRNGKey(1), (128, 64))},
}
d = tempfile.mkdtemp()
save_artifact(d, tree, None, mesh=mesh)

# same mesh: per-shard files go straight onto their owning devices
art = load_artifact(d, mesh=mesh)
qt0, qt1 = tree["blocks"]["attn"]["wq"]["w"], art.params["blocks"]["attn"]["wq"]["w"]
assert np.array_equal(np.asarray(qt0.packed), np.asarray(qt1.packed))
assert np.array_equal(np.asarray(qt0.scale_m), np.asarray(qt1.scale_m))
# elastic fallback: a DIFFERENT mesh shape still assembles correctly
mesh2 = parse_mesh_spec("dp=1,ep=4")
art2 = load_artifact(d, mesh=mesh2)
qt2 = art2.params["blocks"]["attn"]["wq"]["w"]
assert np.array_equal(np.asarray(qt0.packed), np.asarray(qt2.packed))
# mesh-free host assembly of the same sharded files
art3 = load_artifact(d)
qt3 = art3.params["blocks"]["attn"]["wq"]["w"]
assert np.array_equal(np.asarray(qt0.packed), np.asarray(qt3.packed))
assert np.array_equal(
    np.asarray(tree["embed"]["table"]), np.asarray(art3.params["embed"]["table"])
)

# a manifest whose shards no longer tile the array (a host's shards missing)
# must fail verification, not assemble with uninitialized slices
import json
step = art.path
mpath = os.path.join(step, "manifest.json")
man = json.load(open(mpath))
for node in man["nodes"].values():
    for meta in node["arrays"].values():
        if "shards" in meta and len(meta["shards"]) > 1:
            meta["shards"] = meta["shards"][:-1]
with open(mpath, "w") as fh:
    json.dump(man, fh)
try:
    load_artifact(d)
    raise SystemExit("partial shard set restored as intact")
except IOError:
    pass
print("RESTORE_OK")
"""


def test_sharded_artifact_elastic_restore():
    """Sharded payloads restore bit-exact on the saving mesh, on a different
    mesh shape (elastic fallback) and with no mesh at all."""
    r = _run_py(SHARDED_RESTORE_SCRIPT, timeout=240)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "RESTORE_OK" in r.stdout


@pytest.mark.slow
def test_serve_cli_mesh_cold_start(tmp_path):
    """serve.py --artifact DIR --mesh dp=2,ep=2 cold-starts from per-host
    shards and prints the same tokens as the single-device path."""
    art = str(tmp_path / "art")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)

    def serve(*args, timeout=420):
        return subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", *args],
            capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
        )

    r = serve("--arch", "grok-1-314b", "--smoke", "--bits", "2",
              "--group-size", "16", "--backend", "pallas_ep",
              "--requests", "2", "--save-artifact", art,
              "--mesh", "dp=2,ep=2")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "per-host shards" in r.stdout

    def token_lines(out):
        return [l for l in out.splitlines() if l.strip().startswith("req ")]

    single = serve("--artifact", art, "--requests", "4")
    assert single.returncode == 0, single.stdout[-2000:] + single.stderr[-2000:]
    meshed = serve("--artifact", art, "--requests", "4",
                   "--mesh", "dp=2,ep=2")
    assert meshed.returncode == 0, meshed.stdout[-2000:] + meshed.stderr[-2000:]
    assert "per-host shards assembled" in meshed.stdout
    assert token_lines(single.stdout) == token_lines(meshed.stdout)


DENSE_MESH_SCRIPT = r"""
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import QuantConfig
from repro.core.quantizer import QTensor
from repro.launch.mesh import parse_mesh_spec
from repro.models import build_model, quantize_and_plan
from repro.parallel import sharding as rules
from repro.quant import backends

assert jax.device_count() == 4, jax.device_count()

qc = QuantConfig(w_bits=2, group_size=16, mode="ptq", backend="pallas")
cfg = configs.get_smoke("phi4-mini-3.8b", qc)
api = build_model(cfg)
qparams, plan, qapi = quantize_and_plan(api, api.init(jax.random.PRNGKey(0)))
mesh = parse_mesh_spec("dp=2,tp=2")
toks = (jnp.arange(16, dtype=jnp.int32).reshape(2, 8) * 7) % cfg.vocab

def run(m):
    cache = qapi.init_cache(2, 32)
    params = qparams
    if m is not None:
        params = jax.device_put(qparams, rules.qtensor_shardings(qparams, m))
        cache = jax.device_put(cache, rules.cache_shardings(
            jax.eval_shape(lambda: cache), m))
    rules.set_activation_mesh(m)
    try:
        prefill = jax.jit(qapi.prefill_chunk)
        jaxpr = str(jax.make_jaxpr(qapi.prefill_chunk)(
            params, toks, jnp.int32(0), cache))
        logits, cache = prefill(params, toks, jnp.int32(0), cache)
        step, _ = jax.jit(qapi.decode)(
            params, jnp.asarray([[3], [5]], jnp.int32), jnp.int32(8), cache)
    finally:
        rules.set_activation_mesh(None)
    return np.asarray(logits), np.asarray(step), jaxpr

one = run(None)
# the ref oracle must not stand in for a kernel backend anywhere on the path
def boom(*a, **k):
    raise AssertionError("the ref oracle ran under the mesh")
backends._BACKENDS["ref"] = boom
meshed = run(mesh)
jaxpr = meshed[2]
assert "shard_map" in jaxpr, "dense sites did not lower through shard_map"
assert jaxpr.count("pallas_call") >= 2, "dense sites lost their kernels"
assert "psum" in jaxpr, "row-parallel sites (wo, down) did not reduce"
for got, want in zip(meshed[:2], one[:2]):
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert (got.argmax(-1) == want.argmax(-1)).all()

# qmatmul has no layout to shard by: it refuses a kernel backend on a mesh,
# and qdense needs the site's path
qt = jax.tree.leaves(qparams, is_leaf=lambda x: isinstance(x, QTensor))
qt = next(l for l in qt if isinstance(l, QTensor))
qt = jax.tree.map(lambda a: a[0] if a.ndim > 2 else a, qt)
x = jnp.ones((8, qt.k), jnp.float32)
rules.set_activation_mesh(mesh)
for call in (lambda: backends.qmatmul(x, qt, backend="pallas"),
             lambda: backends.qdense(x, qt, backend="pallas")):
    try:
        call()
        raise SystemExit("a kernel backend ran unsharded under the mesh")
    except ValueError:
        pass
rules.set_activation_mesh(None)
print("DENSE_MESH_OK")
"""


def test_dense_sites_run_kernels_under_mesh():
    """Forced 4-device CPU mesh (dp=2, tp=2): every dense site of a PTQ
    model on the pallas backend runs its kernels inside a shard_map over
    the weight's layout (column-parallel sites whole, row-parallel sites
    summed over 'model'), never the ref oracle, and matches the
    one-device logits; qmatmul and a site without its path refuse."""
    r = _run_py(DENSE_MESH_SCRIPT, timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "DENSE_MESH_OK" in r.stdout
