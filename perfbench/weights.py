"""Weights from the seed, made on the device in one jitted call.

The program's own ``init`` gives only the structure (``jax.eval_shape``:
leaf names, shapes, dtypes); every value is drawn here.  Matrices are
normal with variance 1 / fan-in, so activations and logits stay of order
one at every width; RMSNorm gains are ones; embedding rows are unit normal.
The reference (reference.py) reads these arrays by the same names.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "ln_f")


def seed_key(seed: int) -> jax.Array:
    """A key for any whole seed, beyond 32 bits too."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _std(name: str, shape, cfg) -> float:
    if name == "wte":
        return 1.0
    if name in ("wq", "wk", "wv", "wg", "wu", "gate", "head"):
        return 1.0 / math.sqrt(cfg.d_model)
    if name == "wo":
        return 1.0 / math.sqrt(cfg.num_heads * cfg.head_dim)
    if name == "wd":
        return 1.0 / math.sqrt(shape[-2])
    raise ValueError(f"no init rule for weight {name!r} {tuple(shape)}")


def make(model, key: jax.Array) -> Dict[str, Any]:
    """Seeded weights in the structure and dtypes of ``model.init``."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [path[-1].key for path, _ in leaves]
    specs = [leaf for _, leaf in leaves]
    stds = [None if n in NORMS else _std(n, s.shape, model.cfg)
            for n, s in zip(names, specs)]

    def init(key):
        out = []
        for i, (s, std) in enumerate(zip(specs, stds)):
            if std is None:
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                w = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                      jnp.float32) * std
                out.append(w.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(init)(key))
