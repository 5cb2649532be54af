"""Sharding rules: specs valid (divisible) on the production meshes, without
touching device state (AbstractMesh)."""
import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.models import transformer
from repro.sharding import rules


def _mesh(multi_pod=False):
    if multi_pod:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def _check_divisible(tree_abs, tree_specs, mesh):
    for leaf, spec in zip(jax.tree.leaves(tree_abs),
                          jax.tree.leaves(tree_specs,
                                          is_leaf=lambda x: isinstance(x, P))):
        for dim, axes in enumerate(spec):
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else axes
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert leaf.shape[dim] % size == 0, (leaf.shape, spec)


def test_param_specs_divisible_all_archs():
    mesh = _mesh()
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    for arch in configs.ARCHS:
        cfg = configs.get(arch)
        params_abs = jax.eval_shape(
            lambda k, c=cfg: transformer.init_params(k, c), key)
        specs = rules.param_specs(params_abs, mesh)
        _check_divisible(params_abs, specs, mesh)


def test_model_axis_actually_used():
    """Big projection weights must be sharded, not silently replicated."""
    mesh = _mesh()
    cfg = configs.get("llama3.2-1b")
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_abs = jax.eval_shape(lambda k: transformer.init_params(k, cfg), key)
    specs = rules.param_specs(params_abs, mesh)
    blocks = specs["blocks"]
    assert blocks["attn"]["wq"] == P(None, None, "model")
    assert blocks["attn"]["wo"] == P(None, "model", None)
    assert blocks["mlp"]["wg"] == P(None, None, "model")
    assert specs["embed"] == P("model", None)


def test_cache_specs_decode_shapes():
    mesh = _mesh()
    for arch, shape in [("llama3.2-1b", "decode_32k"),
                        ("mamba2-1.3b", "long_500k"),
                        ("recurrentgemma-2b", "decode_32k"),
                        ("yi-9b", "long_500k")]:
        cfg = configs.for_shape(configs.get(arch), shape)
        bsz = configs.SHAPES[shape]["batch"]
        cache_abs = jax.eval_shape(
            lambda c=cfg, b=bsz: transformer.init_cache(
                c, b, configs.cache_len_for(c, shape)))
        specs = rules.cache_specs(cache_abs, mesh)
        _check_divisible(cache_abs, specs, mesh)


def test_batch_specs_long500k_replicates_batch1():
    mesh = _mesh()
    cfg = configs.for_shape(configs.get("yi-9b"), "long_500k")
    batch_abs = configs.input_specs(cfg, "long_500k")
    specs = rules.batch_specs(batch_abs, mesh)
    assert specs["tokens"] == P()           # batch 1 cannot shard over 16
