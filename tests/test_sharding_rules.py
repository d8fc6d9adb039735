"""Sharding-rule unit tests on an abstract 16x16 mesh (no real devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core import stats
from repro.parallel import sharding
from repro.roofline import analysis

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class _Leaf:
    def __init__(self, shape):
        self.shape = shape
        self.ndim = len(shape)
        self.dtype = jnp.float32


def test_projection_rules_train():
    # N-sharded projection: K over data (FSDP), N over model (TP)
    assert sharding.param_spec("blocks/attn/wq/w", _Leaf((4096, 4096)), MESH, "train") == P("data", "model")
    # K-sharded pair member
    assert sharding.param_spec("blocks/attn/wo/w", _Leaf((4096, 4096)), MESH, "train") == P("model", "data")
    assert sharding.param_spec("blocks/mlp/down/w", _Leaf((12288, 4096)), MESH, "train") == P("model", "data")


def test_projection_rules_serve_replicates_data():
    assert sharding.param_spec("blocks/attn/wq/w", _Leaf((4096, 4096)), MESH, "serve") == P(None, "model")
    assert sharding.param_spec("blocks/mlp/down/w", _Leaf((12288, 4096)), MESH, "serve") == P("model", None)


def test_divisibility_fallback():
    # 100 not divisible by 16 -> replicated on that axis
    assert sharding.param_spec("blocks/attn/wq/w", _Leaf((100, 4096)), MESH, "train") == P(None, "model")
    assert sharding.param_spec("blocks/attn/wq/w", _Leaf((4096, 100)), MESH, "train") == P("data", None)


def test_expert_parallelism_when_divisible():
    # 128 experts over model=16 => EP; inner dims lose the model axis
    spec = sharding.param_spec("blocks/moe/experts/gate/w", _Leaf((35, 128, 7168, 4864)), MESH, "train")
    assert spec == P(None, "model", "data", None)
    # 8 experts cannot shard over 16 => TP within experts instead
    spec = sharding.param_spec("blocks/moe/experts/gate/w", _Leaf((64, 8, 6144, 32768)), MESH, "train")
    assert spec == P(None, None, "data", "model")


def test_embedding_and_scalars():
    assert sharding.param_spec("embed/table", _Leaf((131072, 6144)), MESH, "train") == P("model", "data")
    assert sharding.param_spec("embed/table", _Leaf((131072, 6144)), MESH, "serve") == P("model", None)
    assert sharding.param_spec("blocks/ln1/scale", _Leaf((6144,)), MESH, "train") == P(None)


def test_qtensor_fields_shard_like_dense():
    assert sharding.param_spec("blocks/attn/wq/packed", _Leaf((64, 256, 4096)), MESH, "serve") == P(None, None, "model")
    assert sharding.param_spec("blocks/attn/wq/scale_m", _Leaf((64, 64, 4096)), MESH, "serve") == P(None, None, "model")


# ---------------------------------------------------------------------------
# QTensor-aware specs: the decision runs on the logical shape, with packed
# and scale-table projections of K as extra divisibility constraints.
# ---------------------------------------------------------------------------
def _qt(k, n, bits=2, group=16, lead=()):
    """QTensor over ShapeDtypeStructs (no arrays needed for spec logic)."""
    from repro.core.quantizer import INT4_PER_WORD, TERNARY_PER_WORD, QTensor

    wpk = {2: TERNARY_PER_WORD, 4: INT4_PER_WORD, 8: 1}[bits]
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int8)
    return QTensor(
        packed=sds(tuple(lead) + (k // wpk, n)),
        scale_m=sds(tuple(lead) + (k // group, n)),
        scale_e=sds(()),
        bits=bits, group_size=group, shape=(k, n),
    )


def test_qtensor_spec_dispatches_on_logical_shape():
    # logical K=4096 -> K/16 packed rows = 256, K/16 scale rows = 256: all
    # divisible by 16 -> the K-sharded member takes the model axis
    assert sharding.param_spec("blocks/attn/wo/w", _qt(4096, 4096), MESH, "serve") == P("model", None)
    assert sharding.param_spec("blocks/attn/wq/w", _qt(4096, 4096), MESH, "serve") == P(None, "model")


def test_qtensor_packed_dim_divisibility_fallback():
    # logical K=128 divides 16, but the int4 scale table has 128/16=8 rows
    # and the packed payload 128/8=16 rows: 8 % 16 != 0 -> the whole QTensor
    # falls back to replication on K (a dense 128-K leaf would too, but a
    # payload-shape check alone would wrongly shard scale_m here)
    assert sharding.param_spec(
        "blocks/mlp/down/w", _qt(128, 4096, bits=4, group=16), MESH, "serve"
    ) == P(None, None)
    # int8 (words_per_k=1) with the same logical K also falls back: the
    # scale table is the binding constraint
    assert sharding.param_spec(
        "blocks/mlp/down/w", _qt(128, 4096, bits=8, group=16), MESH, "serve"
    ) == P(None, None)


def test_qtensor_field_shardings_consistent():
    qt = _qt(4096, 4096)
    fs = sharding.qtensor_field_shardings("blocks/attn/wo/w", qt, MESH, "serve")
    assert fs.packed.spec == P("model", None)
    assert fs.scale_m.spec == P("model", None)  # scales follow the cluster axis
    assert fs.scale_e.spec == P()
    assert (fs.bits, fs.group_size, fs.shape) == (qt.bits, qt.group_size, qt.shape)


def test_qtensor_expert_stack_ep():
    # stacked experts (E=32, K, N): EP over model, inner dims drop the axis
    qt = _qt(7168, 4864, lead=(32,))
    spec = sharding.param_spec("blocks/moe/experts/gate/w", qt, MESH, "serve")
    assert spec == P("model", None, None)


def _qt_fmt(k, n, fmt, lead=()):
    """Abstract nf4/mx QTensor (the formats whose widths collide with
    built-ins): nf4 packs K/8 uint32 rows like int4; mx stores raw int8
    with a K/32 block-scale table."""
    from repro.core.quantizer import QTensor

    wpk = {"nf4": 8, "mx": 1}[fmt]
    group = 32 if fmt == "mx" else 16
    pdt = jnp.uint32 if fmt == "nf4" else jnp.int8
    sds = lambda shape, dt=jnp.int8: jax.ShapeDtypeStruct(shape, dt)
    return QTensor(
        packed=sds(tuple(lead) + (k // wpk, n), pdt),
        scale_m=sds(tuple(lead) + (k // group, n)),
        scale_e=sds(()),
        bits=4 if fmt == "nf4" else 8, group_size=group, shape=(k, n),
        fmt=fmt,
    )


def test_nf4_qtensor_rules():
    # nf4 halves K like int4 (K/8 packed words): K=4096 -> packed 512 and
    # scale 256 rows, all divisible by 16 -> K-sharded member takes model
    assert sharding.param_spec(
        "blocks/mlp/down/w", _qt_fmt(4096, 4096, "nf4"), MESH, "serve"
    ) == P("model", None)
    # K=128: scale rows 128/16=8 don't divide the 16-wide axis -> the whole
    # QTensor (payload included) falls back together
    assert sharding.param_spec(
        "blocks/mlp/down/w", _qt_fmt(128, 4096, "nf4"), MESH, "serve"
    ) == P(None, None)
    fs = sharding.qtensor_field_shardings(
        "blocks/attn/wq/w", _qt_fmt(4096, 4096, "nf4"), MESH, "serve"
    )
    assert fs.packed.spec == P(None, "model")
    assert fs.scale_m.spec == P(None, "model")
    assert (fs.bits, fs.group_size, fs.fmt) == (4, 16, "nf4")


def test_mx_qtensor_rules():
    # mx scale tables follow their 32-block cluster axis: K=4096 -> 128
    # scale rows, divisible -> K shards; payload (raw int8, words_per_k=1)
    # inherits the same spec
    qt = _qt_fmt(4096, 4096, "mx")
    assert sharding.param_spec("blocks/mlp/down/w", qt, MESH, "serve") == P("model", None)
    fs = sharding.qtensor_field_shardings("blocks/mlp/down/w", qt, MESH, "serve")
    assert fs.packed.spec == P("model", None)
    assert fs.scale_m.spec == P("model", None)  # block axis, not payload K
    assert fs.scale_e.spec == P()
    # K=256: logical and packed K divide 16 but the 256/32=8 scale rows do
    # not -> the 32-block table is the binding constraint, all fields fall
    # back together
    assert sharding.param_spec(
        "blocks/mlp/down/w", _qt_fmt(256, 4096, "mx"), MESH, "serve"
    ) == P(None, None)


def test_block_format_expert_stacks_ep():
    for fmt in ("nf4", "mx"):
        qt = _qt_fmt(7168, 4864, fmt, lead=(32,))
        spec = sharding.param_spec(
            "blocks/moe/experts/gate/w", qt, MESH, "serve"
        )
        assert spec == P("model", None, None), fmt


def test_qtensor_shardings_tree():
    from repro.core.quantizer import QTensor

    tree = {
        "blocks": {"attn": {"wq": {"w": _qt(4096, 4096)}}},
        "ln": {"scale": _Leaf((4096,))},
    }
    sh = sharding.qtensor_shardings(tree, MESH)
    wq = sh["blocks"]["attn"]["wq"]["w"]
    assert isinstance(wq, QTensor)  # QTensor-of-shardings, treedef-compatible
    assert wq.packed.spec == P(None, "model")
    assert sh["ln"]["scale"].spec == P(None)


def test_ep_divisible():
    from repro.quant import ep_divisible

    assert ep_divisible(4, 8, MESH3, "model", ()) is False  # 4 % 16 != 0
    assert ep_divisible(32, 32, MESH, "model", ()) is True
    assert ep_divisible(32, 32, MESH, "model", ("data",)) is False  # C % 512
    assert ep_divisible(32, 32, None) is False


# ---------------------------------------------------------------------------
# Block-format QTensors on a REAL forced 4-device mesh (subprocess: the host
# device count must be set before jax initializes, as in test_dryrun.py).
# ---------------------------------------------------------------------------
_FORCED_MESH_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import parse_mesh_spec
from repro.parallel import sharding
from repro.quant import dequantize_weights, quantize_weights

assert jax.device_count() == 4, jax.device_count()
mesh = parse_mesh_spec("dp=2,tp=2")  # data=2 x model=2

rng = np.random.default_rng(0)
w = jnp.asarray(rng.normal(size=(128, 64)), jnp.float32)
for fmt in ("nf4", "mx"):
    qt = quantize_weights(w, group_size=32, fmt=fmt)
    tree = {"blocks": {"mlp": {"down": {"w": qt}}}}
    sh = sharding.qtensor_shardings(tree, mesh)
    fs = sh["blocks"]["mlp"]["down"]["w"]
    assert fs.packed.spec == P("model", None), (fmt, fs.packed.spec)
    assert fs.scale_m.spec == P("model", None), (fmt, fs.scale_m.spec)
    on_mesh = jax.device_put(tree, sh)
    qts = on_mesh["blocks"]["mlp"]["down"]["w"]
    # each device holds half the packed K rows and half the scale rows
    wpk = {"nf4": 8, "mx": 1}[fmt]
    for shard in qts.packed.addressable_shards:
        assert shard.data.shape == (128 // wpk // 2, 64), (fmt, shard.data.shape)
    for shard in qts.scale_m.addressable_shards:
        assert shard.data.shape == (128 // qt.group_size // 2, 64), fmt
    # the sharded tensor dequantizes bit-identically to the host original
    got = np.asarray(jax.jit(dequantize_weights)(qts))
    want = np.asarray(dequantize_weights(qt))
    assert np.array_equal(got, want), fmt
print("OK")
"""


@pytest.mark.slow  # fresh JAX subprocess (repo convention for forced-device cells)
def test_block_formats_on_forced_4_device_mesh():
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(repo, "src"),
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    r = subprocess.run(
        [sys.executable, "-c", _FORCED_MESH_SCRIPT],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_paper_op_ratio_claims():
    """Sec. 3.3: ~85% multiplies replaced at N=4, ~98% at N=64."""
    approx4 = stats.paper_approximation(4)
    approx64 = stats.paper_approximation(64)
    assert 0.83 <= approx4 <= 0.90
    assert approx64 >= 0.98
    specs = stats.resnet101_specs()
    exact4 = stats.network_replaced_fraction(specs, 4)
    exact64 = stats.network_replaced_fraction(specs, 64)
    assert 0.80 <= exact4 <= 0.95
    assert exact64 >= 0.98


def test_gemm_ratio_and_weight_bytes():
    gemms = [stats.GemmSpec("qkv", 4096, 6144), stats.GemmSpec("attn", 4096, 4096, weight_quantized=False)]
    total, wq_frac, all_frac = stats.network_gemm_stats(gemms, 64)
    assert wq_frac == pytest.approx(1 - 1 / 64)
    assert all_frac < wq_frac
    b2 = stats.weight_bytes(gemms, 2, 64)
    b16 = 4096 * 6144 * 2
    assert b2 < b16 / 6  # >6x HBM compression vs bf16 incl. scale overhead


def test_collective_parse():
    hlo = """
  %ag = bf16[16,1024]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[256]{0} all-reduce(%y), to_apply=%sum
  %t = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all(%a, %b)
  %rs = bf16[4,4]{1,0} reduce-scatter(%z), dimensions={0}
  %cp = u32[10]{0} collective-permute(%w)
  %not_a_collective = f32[999]{0} add(%p, %q)
"""
    got = analysis.collective_bytes(hlo)
    assert got["all-gather"] == 16 * 1024 * 2
    assert got["all-reduce"] == 256 * 4
    assert got["all-to-all"] == 2 * 64 * 4
    assert got["reduce-scatter"] == 16 * 2
    assert got["collective-permute"] == 40
