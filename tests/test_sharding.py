"""Sharding rules: every produced PartitionSpec must divide its dim, for every
assigned architecture, in both modes, on the production mesh shape."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_arch, list_archs
from repro.core import sharding as shd
from repro.launch import steps as st

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
ASSIGNED = [a for a in list_archs() if not a.startswith("basic-")]


def _axis_size(mesh, name):
    if isinstance(name, tuple):
        return int(np.prod([_axis_size(mesh, n) for n in name]))
    return mesh.shape[name]


def _check_divisible(tree_specs, tree_vals, mesh, tag):
    specs = jax.tree_util.tree_leaves_with_path(
        tree_specs, is_leaf=lambda s: isinstance(s, P))
    vals = dict(jax.tree_util.tree_leaves_with_path(tree_vals))
    for path, spec in specs:
        shape = np.shape(vals[path])
        for dim, names in enumerate(spec):
            if names is None:
                continue
            size = _axis_size(mesh, names)
            assert shape[dim] % size == 0, (tag, path, shape, dim, spec)


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("mode", ["basic_ws", "tp"])
@pytest.mark.parametrize("mesh", [MESH, MESH_MP], ids=["pod", "multipod"])
def test_param_specs_divide(arch, mode, mesh):
    cfg = get_arch(arch)
    params_abs = st.abstract_params(cfg)
    specs = shd.params_specs(params_abs, mesh, mode)
    _check_divisible(specs, params_abs, mesh, f"{arch}/{mode}")


@pytest.mark.parametrize("arch", ["qwen3-32b", "mixtral-8x22b",
                                  "jamba-1.5-large-398b", "arctic-480b"])
def test_basic_ws_shards_every_big_matrix(arch):
    """Paper §5.1: weights (>=2D) must actually be split, not replicated —
    else the memory saving evaporates."""
    cfg = get_arch(arch)
    params_abs = st.abstract_params(cfg)
    specs = shd.params_specs(params_abs, MESH, "basic_ws")
    leaves = jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda s: isinstance(s, P))
    vals = dict(jax.tree_util.tree_leaves_with_path(params_abs))
    unsharded_big = [
        (p, np.shape(vals[p])) for p, s in leaves
        if s == P() and np.prod(np.shape(vals[p])) > 1e6]
    assert not unsharded_big, unsharded_big


def test_tp_moe_expert_axis():
    """128-expert Arctic shards the expert axis; 8-expert Mixtral falls back
    to intra-expert TP on the ff dim."""
    for arch, expect_axis in (("arctic-480b", 1), ("mixtral-8x22b", None)):
        cfg = get_arch(arch)
        params_abs = st.abstract_params(cfg)
        specs = shd.params_specs(params_abs, MESH, "tp")
        moe_wi = None
        for path, s in jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda s: isinstance(s, P)):
            sp = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                          for k in path)
            if sp.endswith("moe/wi"):
                moe_wi = s
                break
        assert moe_wi is not None
        if expect_axis == 1:
            assert moe_wi[1] == "model", moe_wi      # expert parallel
        else:
            assert moe_wi[1] is None and "model" in tuple(moe_wi), moe_wi


def test_batch_specs_shard_over_data_axes():
    cfg = get_arch("llama3.2-1b")
    ins = st.input_specs(cfg, INPUT_SHAPES["train_4k"])
    specs = shd.batch_specs(ins, MESH_MP)
    assert specs["tokens"][0] == ("pod", "data")


def test_cache_specs_context_parallel_for_batch_1():
    """long_500k (batch=1): the cache sequence axis gets sharded instead."""
    cfg = get_arch("llama3.2-1b")  # SWA ring cache of 8192
    ins = st.input_specs(cfg, INPUT_SHAPES["long_500k"])
    specs = shd.cache_specs(ins["caches"], MESH)
    flat = jax.tree_util.tree_leaves(specs,
                                     is_leaf=lambda s: isinstance(s, P))
    assert any(any(ax is not None for ax in s[2:]) for s in flat
               if len(s) > 2), flat


def test_replicated_mode_is_all_empty_specs():
    cfg = get_arch("mamba2-130m")
    params_abs = st.abstract_params(cfg)
    specs = shd.params_specs(params_abs, MESH, "replicated")
    for s in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P)):
        assert s == P()


def test_params_specs_largest_axis_not_divisible_falls_back():
    """basic_ws must shard the largest DIVISIBLE dim: when the largest axis
    of a leaf doesn't divide the model-axis size, the next-largest
    divisible one is used, and a leaf with no divisible dim >= the axis
    size stays replicated (never a crash, never an invalid spec)."""
    SDS = jax.ShapeDtypeStruct
    f32 = np.float32
    tree = {
        # largest dim 100 not divisible by 16; dim 64 is -> shard axis 1
        "w_fallback": SDS((100, 64), f32),
        # no dim divisible by 16 -> replicated
        "w_odd": SDS((100, 30), f32),
        # dim 16 == axis size exactly -> shardable
        "w_exact": SDS((16, 10), f32),
        # divisible but smaller than axis size never selected (48 % 16 == 0
        # and 48 >= 16 -> sharded on axis 0, the largest divisible)
        "w_mixed": SDS((48, 100), f32),
    }
    specs = shd.params_specs(tree, MESH, "basic_ws")
    assert specs["w_fallback"] == P(None, "model")
    assert specs["w_odd"] == P()
    assert specs["w_exact"] == P("model", None)
    assert specs["w_mixed"] == P("model", None)
    _check_divisible(specs, tree, MESH, "fallback")


def test_params_specs_stacked_blocks_never_shard_scan_axis():
    """A 'blocks' leaf whose LARGEST axis is the leading scan axis must not
    shard it, even when divisible — the scan axis is iteration order, not
    a weight dim."""
    SDS = jax.ShapeDtypeStruct
    tree = {"blocks": {"w": SDS((32, 16, 10), np.float32)}}
    specs = shd.params_specs(tree, MESH, "basic_ws")
    # axis 0 (32, divisible) is skipped; axis 1 (16) is the fallback
    assert specs["blocks"]["w"] == P(None, "model", None)


def test_batch_specs_explicit_batch_axes_override():
    """batch_axes overrides the default ('pod','data') distribution — the
    paper's §5.1 'batch over ALL cores' layout adds the model axis."""
    SDS = jax.ShapeDtypeStruct
    batch = {"tokens": SDS((512, 128), np.int32),
             "scalar": SDS((), np.float32)}
    specs = shd.batch_specs(batch, MESH_MP,
                            batch_axes=("pod", "data", "model"))
    assert specs["tokens"] == P(("pod", "data", "model"), None)
    assert specs["scalar"] == P()
    # axes that don't divide are dropped left-to-right: batch 24 fits pod=2
    # and nothing more on the 2x16x16 mesh
    small = shd.batch_specs({"t": SDS((24, 4), np.int32)}, MESH_MP,
                            batch_axes=("pod", "data", "model"))
    assert small["t"] == P(("pod",), None)
