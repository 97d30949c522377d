"""Sharding-rule tests: every param/cache/batch spec must be valid
(divisible, axis-unique) for every arch on the production meshes — checked
against AbstractMesh so no 512-device runtime is needed."""

import jax
import jax.numpy as jnp
import pytest

from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.launch import sharding as shd
from repro.models import build_model

MESH_1POD = AbstractMesh((16, 16), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)


def _axis_sz(mesh, axis):
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _check_tree(tree, specs, mesh):
    leaves = jax.tree.leaves(tree)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves)
    for leaf, spec in zip(leaves, spec_leaves):
        assert isinstance(spec, P)
        used = []
        assert len(spec) <= len(leaf.shape)
        for d, axis in enumerate(spec):
            if axis is None:
                continue
            names = axis if isinstance(axis, tuple) else (axis,)
            for nm in names:
                assert nm not in used, (spec, leaf.shape)
                used.append(nm)
            assert leaf.shape[d] % _axis_sz(mesh, axis) == 0, \
                (spec, leaf.shape, d)


@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD], ids=["1pod", "2pod"])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_valid_all_archs(arch, mesh):
    cfg = get_config(arch)  # FULL config — shapes must divide for real dims
    model = build_model(cfg)
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(params_sds, mesh)
    _check_tree(params_sds, specs, mesh)


@pytest.mark.parametrize("arch", ["tinyllama_1_1b", "deepseek_v2_236b",
                                  "gemma_2b", "zamba2_1_2b", "rwkv6_7b"])
def test_cache_specs_valid(arch):
    from repro.models import lm

    cfg = get_config(arch)
    cache_sds = jax.eval_shape(lambda: lm.init_cache(cfg, 128, 1024))
    specs = shd.cache_specs(cache_sds, MESH_1POD)
    _check_tree(cache_sds, specs, MESH_1POD)


def test_model_axis_engaged_for_key_tensors():
    """TP sanity: tinyllama q heads (32) shard over model=16, kv (4) do
    not; granite experts (40) fall back to TP-within-expert."""
    cfg = get_config("tinyllama_1_1b")
    model = build_model(cfg)
    sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(sds, MESH_1POD)
    def at(spec, shape_len, negdim):
        t = tuple(spec) + (None,) * (shape_len - len(tuple(spec)))
        return t[negdim]

    wq = specs["layers"][0]["attn"]["wq"]
    assert at(wq, 4, -2) == "model"    # 32 q heads sharded (stacked: 4 dims)
    wk = specs["layers"][0]["attn"]["wk"]
    assert at(wk, 4, -2) is None       # 4 kv heads not divisible
    g = get_config("granite_moe_3b_a800m")
    gm = build_model(g)
    gsds = jax.eval_shape(gm.init, jax.random.PRNGKey(0))
    gspecs = shd.param_specs(gsds, MESH_1POD)
    # granite: 40 experts % 16 != 0 -> expert dim unsharded, F dim takes model
    layer = gspecs["layers"][0]["ffn"]
    assert at(layer["wi"], 4, -3) is None and at(layer["wi"], 4, -1) == "model"


def test_batch_spec_fallback_chain():
    spec = shd.batch_specs({"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32)},
                           MESH_1POD, profile="fsdp")
    assert spec["tokens"][0] == ("data", "model")  # 256 over all 256
    spec = shd.batch_specs({"tokens": jax.ShapeDtypeStruct((128, 8), jnp.int32)},
                           MESH_1POD, profile="fsdp")
    assert spec["tokens"][0] == "data"  # 128 % 256 != 0 -> data only
    spec = shd.batch_specs({"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)},
                           MESH_1POD, profile="fsdp")
    assert spec["tokens"][0] is None  # batch 1: replicate


def test_embed_not_fsdp_sharded_on_dmodel():
    """Regression: sharding the embedding's d_model over "data" made XLA
    psum (B,C,V) logits chunks — ~190 GiB/device on a 16x16 mesh."""
    cfg = get_config("gemma_2b")
    model = build_model(cfg)
    sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(sds, MESH_1POD)
    emb = tuple(specs["embed"]["tok"])
    assert emb[0] == "model" and (len(emb) < 2 or emb[1] is None)
