"""Partition rules: divisibility guards, layout selection, spec coverage."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.launch.sharding import (attn_layout, cache_pspec_tree,
                                   param_pspec_tree)
from repro.models import model as M

MESH = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
MESH_MP = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_attn_layout_per_arch():
    from repro.launch.sharding import attn_layouts
    assert attn_layout(get_config("qwen1.5-0.5b"), 16) == "heads"
    assert attn_layout(get_config("phi3-mini-3.8b"), 16) == "heads"
    # q-heads shard; kv (8 heads) stays replicated over model
    assert attn_layouts(get_config("qwen3-4b"), 16) == (("model", None), (None, None))
    assert attn_layouts(get_config("llama-3.2-vision-90b"), 16) == (
        ("model", None), (None, None))
    assert attn_layout(get_config("arctic-480b"), 16) == "head_dim"  # H=56
    assert attn_layout(get_config("smollm-360m"), 16) == "head_dim"  # 15/5


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "arctic-480b",
                                  "mamba2-2.7b", "zamba2-2.7b",
                                  "llama-3.2-vision-90b", "smollm-360m"])
def test_param_specs_cover_and_divide(arch):
    cfg = get_config(arch)
    shapes = M.param_shapes(cfg)
    specs = param_pspec_tree(cfg, MESH, shapes)
    n_sharded = 0
    for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_flatten_with_path(specs)[0]):
        assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
        for dim, entry in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for a in axes:
                total *= dict(MESH.shape)[a]
            assert dim % total == 0, (path, spec, leaf.shape)
            n_sharded += 1
    assert n_sharded > 0, "nothing sharded at all"


def test_big_weights_are_sharded():
    """Every leaf >= 1M params must be sharded on at least one axis."""
    import numpy as np
    for arch in ("arctic-480b", "llama-3.2-vision-90b", "mamba2-2.7b"):
        cfg = get_config(arch)
        shapes = M.param_shapes(cfg)
        specs = param_pspec_tree(cfg, MESH, shapes)
        for (path, leaf), (_, spec) in zip(
                jax.tree_util.tree_flatten_with_path(shapes)[0],
                jax.tree_util.tree_flatten_with_path(specs)[0]):
            if np.prod(leaf.shape) >= 1_000_000:
                assert any(e is not None for e in spec), (arch, path, leaf.shape)


def test_smollm_attention_weights_shard_head_dim():
    cfg = get_config("smollm-360m")
    shapes = M.param_shapes(cfg)
    specs = param_pspec_tree(cfg, MESH, shapes)
    wq = specs["stack"]["layers"]["attn"]["wq"]["w"]
    # [L, d_model, 15, 64]: heads dim must NOT be sharded, head_dim is
    assert wq[2] is None and wq[3] == "model", wq


def test_cache_specs_divide(tmp_path):
    for arch in ("qwen3-4b", "mamba2-2.7b", "zamba2-2.7b",
                 "llama-3.2-vision-90b"):
        cfg = get_config(arch)
        spec_tree = M.make_decode_cache_spec(cfg, 128, 1024)
        specs = cache_pspec_tree(cfg, MESH, spec_tree)
        for (path, leaf), (_, spec) in zip(
                jax.tree_util.tree_flatten_with_path(spec_tree)[0],
                jax.tree_util.tree_flatten_with_path(specs)[0]):
            for dim, entry in zip(leaf.shape, tuple(spec) + (None,) * 8):
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                total = 1
                for a in axes:
                    total *= dict(MESH.shape)[a]
                assert dim % total == 0, (arch, path, spec, leaf.shape)


def test_multipod_specs_build():
    cfg = get_config("qwen3-4b")
    shapes = M.param_shapes(cfg)
    specs = param_pspec_tree(cfg, MESH_MP, shapes)
    assert len(jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))) > 0
