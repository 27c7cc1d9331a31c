"""Sharding rules over the production mesh shapes (AbstractMesh — no
devices needed) + divisibility guarantees for every assigned arch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, ASSIGNED, SHAPES, get_arch, supports_shape
from repro.distributed import sharding as shd


def mesh_single():
    return AbstractMesh((16, 16), ("data", "model"))


def mesh_multi():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class FakeLeaf:
    def __init__(self, shape):
        self.shape = shape


@pytest.mark.parametrize("mesh_fn", [mesh_single, mesh_multi])
def test_row_column_rules(mesh_fn):
    mesh = mesh_fn()
    # row-parallel: contraction dim sharded
    spec = shd.param_pspec("wq", FakeLeaf((4096, 2048)), mesh,
                           zero3=False, stacked=False)
    assert spec[0] == "model" and spec[1] is None
    # column-parallel
    spec = shd.param_pspec("w_up", FakeLeaf((4096, 16384)), mesh,
                           zero3=False, stacked=False)
    assert spec[1] == "model"
    # stacked leading dim never sharded
    spec = shd.param_pspec("wq", FakeLeaf((24, 4096, 2048)), mesh,
                           zero3=False, stacked=True)
    assert spec[0] is None and spec[1] == "model"


def test_moe_expert_parallel_when_divisible():
    mesh = mesh_single()
    spec = shd.param_pspec("w_gate", FakeLeaf((94, 128, 4096, 1536)),
                           mesh, zero3=True, stacked=True)
    assert spec[1] == "model"       # 128 experts / 16
    spec8 = shd.param_pspec("w_gate", FakeLeaf((56, 8, 6144, 16384)),
                            mesh, zero3=False, stacked=True)
    assert spec8[1] != "model"      # 8 experts not divisible -> TP


def test_indivisible_falls_back():
    mesh = mesh_single()
    # hubert vocab=504 not divisible by 16
    spec = shd.param_pspec("embed", FakeLeaf((504, 1280)), mesh,
                           zero3=False, stacked=False)
    for entry in spec:
        if entry is not None:
            axes = (entry,) if isinstance(entry, str) else entry
            sz = int(np.prod([mesh.shape[a] for a in axes]))
            dim = spec.index(entry)
            assert FakeLeaf((504, 1280)).shape[dim] % sz == 0


@pytest.mark.parametrize("shape_name", list(SHAPES))
@pytest.mark.parametrize("mesh_fn", [mesh_single, mesh_multi])
def test_data_specs_divisible(shape_name, mesh_fn):
    mesh = mesh_fn()
    shape = SHAPES[shape_name]
    spec = shd.data_pspec(shape, mesh, 2)
    sizes = (shape.global_batch, shape.seq_len)
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else entry
        sz = int(np.prod([mesh.shape[a] for a in axes]))
        assert sizes[dim] % sz == 0


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("mesh_fn", [mesh_single, mesh_multi])
def test_every_param_spec_divisible(arch, mesh_fn):
    """Choose specs for every real parameter of every arch; all sharded
    dims must divide the axis product — guarantees lowering."""
    import functools
    from repro.models import transformer
    cfg = get_arch(arch)
    mesh = mesh_fn()
    abs_params = jax.eval_shape(
        functools.partial(transformer.init_params, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32))

    def check(path, leaf):
        stacked = any(getattr(p, "key", None) == "blocks" for p in path)
        name = ""
        for p in reversed(path):
            key = getattr(p, "key", None)
            if isinstance(key, str):
                name = key
                break
        spec = shd.param_pspec(name, leaf, mesh, zero3=cfg.zero3,
                               stacked=stacked)
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            axes = (entry,) if isinstance(entry, str) else entry
            sz = int(np.prod([mesh.shape[a] for a in axes]))
            assert leaf.shape[dim] % sz == 0, (name, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(check, abs_params)


def test_long_context_shards_sequence():
    mesh = mesh_single()
    spec = shd.data_pspec(SHAPES["long_500k"], mesh, 2)
    assert spec[0] is None and spec[1] is not None


def test_cache_spec():
    mesh = mesh_single()
    spec = shd.cache_pspec(SHAPES["decode_32k"], mesh, 5)
    assert spec[0] is None            # layer stack dim
    assert spec[1] is not None        # batch
    assert spec[2] == "model"         # sequence over model
