"""Sharding rules: every (arch × mesh) param/cache spec must respect
divisibility (axes only assigned when the dim divides), and key tensors must
actually be distributed."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import ASSIGNED, get_config
from repro.models import sharding as SH
from repro.models.registry import build_model


def _mesh(multi_pod=False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return AbstractMesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def _axis_size(mesh, axes):
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
                        for a in axes]))


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
@pytest.mark.parametrize("multi_pod", [False, True])
@pytest.mark.parametrize("mode", ["train", "serve"])
def test_param_specs_divisible(arch, multi_pod, mode):
    cfg = get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = _mesh(multi_pod)
    specs = SH.param_pspecs(cfg, shapes, mesh, mode=mode)
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    for leaf, spec in zip(flat_shapes, flat_specs):
        assert len(spec) <= len(leaf.shape)
        for dim, axes in zip(leaf.shape, spec):
            size = _axis_size(mesh, axes)
            assert dim % size == 0, (arch, leaf.shape, spec)


@pytest.mark.parametrize("arch", ["nemotron-4-340b", "command-r-35b",
                                  "mixtral-8x7b", "deepseek-v2-lite-16b"])
def test_big_weights_are_sharded_in_train(arch):
    """No >1 GB parameter may stay fully replicated under the train rules."""
    cfg = get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = _mesh(False)
    specs = SH.param_pspecs(cfg, shapes, mesh, mode="train")
    for leaf, spec in zip(jax.tree.leaves(shapes),
                          jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if nbytes > 1e9:
            shards = int(np.prod([_axis_size(mesh, a) for a in spec]))
            assert shards >= 16, (arch, leaf.shape, spec)


def test_expert_parallel_when_divisible():
    """deepseek (E=64) shards experts over model; mixtral (E=8) falls back to
    tensor-parallel d_ff."""
    mesh = _mesh(False)
    ds = get_config("deepseek-v2-lite-16b")
    m = build_model(ds)
    shapes = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    specs = SH.param_pspecs(ds, shapes, mesh, mode="train")
    assert specs["layers"]["moe"]["wg"][1] == "model"     # [L, E, d, f] EP
    mx = get_config("mixtral-8x7b")
    m = build_model(mx)
    shapes = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    specs = SH.param_pspecs(mx, shapes, mesh, mode="train")
    assert specs["layers"]["moe"]["wg"][1] != "model"
    assert specs["layers"]["moe"]["wg"][3] == "model"     # TP over f


def test_mqa_kv_not_sharded_seq_cache_instead():
    """granite (kv=1): kv heads can't shard over model=16 — the cache rules
    shard the sequence dim instead (distributed flash-decode)."""
    cfg = get_config("granite-20b")
    model = build_model(cfg)
    mesh = _mesh(False)
    cache = jax.eval_shape(lambda: model.init_cache(128, 32768))
    specs = SH.cache_pspecs(cfg, cache, mesh)
    k_spec = specs["layers"]["k"]            # [L, B, S, Hkv, hd]
    assert k_spec[2] == "model"              # seq sharded
    assert k_spec[3] is None


def test_serve_mode_weight_gather_for_big_models():
    """340B can't replicate per data shard: serve rules keep FSDP sharding."""
    cfg = get_config("nemotron-4-340b")
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = _mesh(False)
    specs = SH.param_pspecs(cfg, shapes, mesh, mode="serve")
    wq = specs["layers"]["attn"]["wq"]       # [L, d, H, hd]
    assert wq[1] is not None                 # fsdp axis on
    small = get_config("llama3.2-3b")
    m2 = build_model(small)
    shapes2 = jax.eval_shape(lambda: m2.init(jax.random.PRNGKey(0)))
    specs2 = SH.param_pspecs(small, shapes2, mesh, mode="serve")
    assert specs2["layers"]["attn"]["wq"][1] is None     # replicated over data
