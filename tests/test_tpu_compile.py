"""Compile the serving kernels for a TPU v5e that is described, not attached.

Interpret-mode parity tests run the kernel bodies on the CPU and never see
Mosaic's refusals (int8 arithmetic it cannot legalize, block shapes off the
(8, 128) tiling).  Here each kernel of the serving path is lowered with
``interpret=False`` and compiled by the TPU compiler at Phi-4-mini widths
(d 3072, ff 8192, 24 query / 8 KV heads, head_dim 128): decode M = 8 and a
256-token prefill chunk; ``quantize_rows`` also at grok-1's d_ff, 32768;
and the dense site sharded over a 2x2 mesh, as ``serve.py --mesh`` runs
it.  Nothing runs; a compile that passes says nothing about results or speed.

The topology is described inside the ``topo`` fixture only, never at import:
only one process may load the TPU library, so every test worker must
collect the same tests and only the one given this file may load it.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_prefill import flash_attend
from repro.kernels.quantize import quantize_rows
from repro.models.kv_cache import MX_KV_BLOCK
from repro.parallel import sharding as rules
from repro.quant import backends
from repro.quant.formats import format_names, get_format, quantize_weights

D_MODEL, D_FF, N_KV_HEADS, GQA_GROUP, HEAD_DIM = 3072, 8192, 8, 3, 128
GROUP = 16  # cluster size chip_smoke.py serves
MAX_LEN, SLOTS, CHUNK = 1024, 8, 256
FUSED_FORMATS = [f for f in format_names() if get_format(f).fused_kernel]
# the weight format each fused kernel is named after (``fused_qmm_<fmt>``):
# mx runs the int8 kernels
KERNEL_FMT = {"mx": "int8"}
# (K, N, epilogue activation): the gate projection and the down projection
SITES = [(D_MODEL, D_FF, "silu"), (D_FF, D_MODEL, None)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described v5e device, with the persistent compile cache off: an
    entry written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def test_fused_formats_cover_the_builtins():
    assert set(FUSED_FORMATS) >= {"ternary", "int4", "int8", "nf4", "mx"}


@pytest.mark.parametrize("m", [8, CHUNK], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n,act", SITES, ids=["gate", "down"])
@pytest.mark.parametrize("fmt", FUSED_FORMATS)
def test_fused_qdense_compiles(one_chip, monkeypatch, fmt, k, n, act, m):
    # the serving path decides interpret mode from the platform, which is
    # the CPU here; steer it to the compiled kernel the chip would run
    monkeypatch.setattr(backends, "interpret_mode", lambda: False)
    qt = jax.eval_shape(
        lambda: quantize_weights(jnp.zeros((k, n)), fmt=fmt, group_size=GROUP)
    )
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    step = jax.jit(lambda x, qt: backends.qdense(x, qt, act=act, backend="pallas"))
    text = step.lower(x, _on(one_chip, qt)).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"%fused_qmm_{KERNEL_FMT.get(fmt, fmt)}" in text


@pytest.mark.parametrize(
    "m,d,dtype",
    [(8, D_MODEL, jnp.bfloat16), (CHUNK, D_MODEL, jnp.bfloat16),
     (64, 32768, jnp.float32)],
    ids=["decode", "prefill", "grok_ff"],  # grok-1's d_ff: f32 expert rows
)
def test_quantize_rows_compiles(one_chip, m, d, dtype):
    x = jax.ShapeDtypeStruct((m, d), dtype, sharding=one_chip)
    fn = jax.jit(lambda x: quantize_rows(x, interpret=False))
    text = fn.lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%quantize_rows" in text


def _cache_leaves(fmt, b):
    shape = (b, MAX_LEN, N_KV_HEADS, HEAD_DIM)
    if fmt == "kv_bf16":
        return (jax.ShapeDtypeStruct(shape, jnp.bfloat16),) * 2 + (None, None)
    if fmt == "kv_int8":
        kv = jax.ShapeDtypeStruct(shape, jnp.int8)
        e = jax.ShapeDtypeStruct(shape[:-1] + (1,), jnp.int8)
        return kv, kv, e, e
    kv = jax.ShapeDtypeStruct(shape[:-1] + (HEAD_DIM // 2,), jnp.uint8)
    e = jax.ShapeDtypeStruct(
        (b, MAX_LEN // MX_KV_BLOCK, N_KV_HEADS, 1), jnp.int8
    )
    return kv, kv, e, e


@pytest.mark.parametrize(
    "b,s", [(SLOTS, 1), (1, CHUNK)], ids=["decode", "prefill"]
)
@pytest.mark.parametrize("fmt", ["kv_bf16", "kv_int8", "kv_mx"])
def test_flash_attend_compiles(one_chip, fmt, b, s):
    k, v, ke, ve = _cache_leaves(fmt, b)
    q = jax.ShapeDtypeStruct((b, s, N_KV_HEADS, GQA_GROUP, HEAD_DIM), jnp.float32)
    rows = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    win = jax.ShapeDtypeStruct((1, 1), jnp.int32)

    def attend(q, k, v, ke, ve, q_start, valid, window):
        return flash_attend(
            q, k, v, ke, ve, q_start, valid, window, fmt=fmt, interpret=False
        )

    args = _on(one_chip, (q, k, v, ke, ve, rows, rows, win))
    text = jax.jit(attend).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%flash_attend" in text


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


@pytest.mark.parametrize(
    "site,k,n", [("blocks/mlp/gate", D_MODEL, D_FF),
                 ("blocks/mlp/down", D_FF, D_MODEL)],
    ids=["column_parallel", "row_parallel"],
)
def test_sharded_qdense_compiles(one_chip, mesh_2x2, monkeypatch, site, k, n):
    # a dense site under a multi-device mesh: its kernels inside a
    # shard_map over the weight's serve layout (N or K over 'model')
    monkeypatch.setattr(backends, "interpret_mode", lambda: False)
    qt = jax.eval_shape(
        lambda: quantize_weights(jnp.zeros((k, n)), fmt="ternary",
                                 group_size=GROUP)
    )
    layout = rules.qtensor_field_shardings(site, qt, mesh_2x2, "serve")
    qt = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        qt, layout,
    )
    x = jax.ShapeDtypeStruct((SLOTS, k), jnp.bfloat16,
                             sharding=NamedSharding(mesh_2x2, P("data", None)))
    step = jax.jit(
        lambda x, qt: backends.qdense(x, qt, backend="pallas", site=site)
    )
    rules.set_activation_mesh(mesh_2x2)
    try:
        text = step.lower(x, qt).compile().as_text()
    finally:
        rules.set_activation_mesh(None)
    assert "tpu_custom_call" in text
    # the kernels by name: with K over 'model' the rows are quantized
    # alone, then the unfused matmul runs
    names = (("%quantize_rows", "%packed_qmm_ternary") if site.endswith("down")
             else ("%fused_qmm_ternary",))
    assert all(name in text for name in names)
    if site.endswith("down"):  # K over 'model': the partials are summed
        assert "all-reduce" in text
