"""Seed-made packed weights against the program's own PTQ tree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights as W
from repro import configs
from repro.configs.base import QuantConfig
from repro.core.quantizer import QTensor
from repro.models import build_model, init_and_quantize
from repro.quant.formats import decode_codes

QC = QuantConfig(w_bits=2, group_size=16, mode="ptq", backend="pallas")


def _leaves(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, QTensor))[0]


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "grok-1-314b"])
def test_tree_matches_ptq(arch):
    api = build_model(configs.get_smoke(arch, QC))
    want, plan, qapi, _ = init_and_quantize(api, jax.random.PRNGKey(0))
    got = W.make_params(api, plan, seed=2**31 + 5)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (path, g), (_, w) in zip(_leaves(got), _leaves(want)):
        assert type(g) is type(w), path
        if isinstance(w, QTensor):
            assert (g.bits, g.group_size, g.shape, g.fmt) == \
                (w.bits, w.group_size, w.shape, w.fmt), path
            for a, b in ((g.packed, w.packed), (g.scale_m, w.scale_m),
                         (g.scale_e, w.scale_e)):
                assert (a.shape, a.dtype) == (b.shape, b.dtype), path
        else:
            assert (g.shape, g.dtype) == (w.shape, w.dtype), path


def test_codes_in_range_and_seeded():
    api = build_model(configs.get_smoke("phi4-mini-3.8b", QC))
    _, plan, qapi, _ = init_and_quantize(api, jax.random.PRNGKey(0))
    a = W.make_params(api, plan, seed=7)
    b = W.make_params(api, plan, seed=7)
    c = W.make_params(api, plan, seed=8)
    wq = a["blocks"]["attn"]["wq"]["w"]
    codes = np.asarray(jax.vmap(decode_codes)(wq))
    assert set(np.unique(codes)) <= {-1, 0, 1}
    assert 0.3 < np.mean(codes != 0) < 0.9
    head = np.asarray(decode_codes(a["lm_head"]["w"]))
    assert np.abs(head).max() <= 127
    same = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)
    assert all(jax.tree.leaves(same))
    assert not np.array_equal(np.asarray(wq.packed),
                              np.asarray(c["blocks"]["attn"]["wq"]["w"].packed))


def test_serves_prefill_and_decode():
    cfg = configs.get_smoke("phi4-mini-3.8b", QC)
    import dataclasses

    cfg = dataclasses.replace(cfg, kv_fmt="kv_int8", flash_decode=True,
                              flash_prefill=True)
    api = build_model(cfg)
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    plan = api.ctx.policy.compile(shapes, mode="ptq", backend="pallas")
    qapi = api.with_plan(plan)
    params = W.make_params(api, plan, seed=3)
    cache = qapi.init_cache(1, 64)
    toks = jnp.arange(16, dtype=jnp.int32)[None]
    logits, cache = qapi.prefill_chunk(params, toks, jnp.int32(0), cache)
    step, _ = qapi.decode(params, jnp.asarray([[3]], jnp.int32),
                          jnp.int32(16), cache)
    assert logits.shape[-1] == cfg.padded_vocab
    assert bool(jnp.isfinite(step).all())
