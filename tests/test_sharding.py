"""Sharding rules: spec structure, divisibility fallbacks, input specs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as cfgs
from repro.configs.shapes import input_specs, is_applicable
from repro.models import init_params
from repro.train.sharding import batch_pspec_for, cache_pspecs, param_pspecs
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh():
    # 1x1 mesh: exercises the full rule engine (axis sizes 1 divide all)
    return make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch", cfgs.ARCHS)
def test_param_specs_cover_tree_and_rank(arch, mesh):
    cfg = cfgs.get_smoke_config(arch)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    specs = param_pspecs(cfg, mesh)
    assert jax.tree.structure(shapes, is_leaf=lambda x: hasattr(x, "shape")) \
        == jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P))
    for sh, sp in zip(jax.tree.leaves(shapes),
                      jax.tree.leaves(specs,
                                      is_leaf=lambda x: isinstance(x, P))):
        assert len(sp) <= len(sh.shape), (sh.shape, sp)


def test_divisibility_fallback():
    """Dims not divisible by the axis are replicated, never mis-sharded."""
    from repro.train.sharding import _leaf_spec

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    # MoE expert weights: params are ZeRO-1 (model-only, no FSDP gather)…
    spec = _leaf_spec(["layers", "moe", "w_gate"], (32, 8, 4096, 14336),
                      FakeMesh())
    assert spec == P(None, None, None, "model")
    # …while the optimizer moments keep the dense 2-D shard
    spec = _leaf_spec(["layers", "moe", "w_gate"], (32, 8, 4096, 14336),
                      FakeMesh(), for_optimizer=True)
    assert spec == P(None, None, "data", "model")
    # and w_down is row-parallel (contraction f on model)
    spec = _leaf_spec(["layers", "moe", "w_down"], (32, 8, 14336, 4096),
                      FakeMesh())
    assert spec == P(None, None, "model", None)
    # vocab divisible -> embedding model-sharded
    spec = _leaf_spec(["embed", "embedding"], (51200, 1024), FakeMesh())
    assert spec == P("model", None)
    # odd vocab -> replicated
    spec = _leaf_spec(["embed", "embedding"], (51865, 1024), FakeMesh())
    assert spec == P(None, None)


@pytest.mark.parametrize("arch", cfgs.ARCHS)
@pytest.mark.parametrize("shape_name", ["train_4k", "prefill_32k",
                                        "decode_32k", "long_500k"])
def test_input_specs_exist_for_every_combo(arch, shape_name):
    cfg = cfgs.get_config(arch)
    ok, reason = is_applicable(cfg, shape_name)
    if not ok:
        assert reason
        return
    specs = input_specs(cfg, shape_name)
    leaves = jax.tree.leaves(specs)
    assert leaves and all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    if shape_name in ("decode_32k", "long_500k"):
        assert specs["tokens"].shape[1] == 1      # ONE new token


def test_long_500k_skips_match_design():
    """Sub-quadratic archs run long_500k; pure full-attention archs skip."""
    should_run = {"falcon_mamba_7b", "zamba2_2_7b", "mixtral_8x7b"}
    for arch in cfgs.ARCHS:
        cfg = cfgs.get_config(arch)
        ok, _ = is_applicable(cfg, "long_500k")
        assert ok == (arch in should_run), arch


def test_batch_pspec_fallback():
    mesh = make_mesh((1, 1), ("data", "model"))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
    specs = batch_pspec_for(batch, mesh)
    assert specs["tokens"] == P("data", None)
    # batch=1 cannot shard on a >1 data axis -> replicated; on size-1 it can
    batch1 = {"tokens": jax.ShapeDtypeStruct((1, 16), jnp.int32)}
    specs1 = batch_pspec_for(batch1, mesh)
    assert specs1["tokens"] == P("data", None)   # 1 % 1 == 0


def test_policy_constrain_noop_without_mesh():
    import jax.numpy as jnp
    from repro.models import policy
    assert policy.get_mesh() is None
    x = jnp.ones((4, 8))
    assert policy.constrain(x, "batch", None) is x


def test_policy_constrain_with_mesh():
    import jax
    import jax.numpy as jnp
    from repro.models import policy
    mesh = make_mesh((1, 1), ("data", "model"))
    with policy.use_mesh(mesh):
        x = jnp.ones((4, 8))
        y = policy.constrain(x, "batch", "model")
        assert y.shape == x.shape
        # non-divisible dim falls back to replicated rather than erroring
        z = policy.constrain(jnp.ones((3, 5)), "batch", "model")
        assert z.shape == (3, 5)
    assert policy.get_mesh() is None
