"""Weights from the seed, made on the device in one jitted call.

The program's own ``init`` gives only the structure (``jax.eval_shape``:
leaf names, shapes, dtypes); every value is drawn here.  Matrices are
normal with variance 1 / fan-in, so activations and logits stay of order
one at every width; RMSNorm gains are ones; embedding rows are unit normal.
A leaf's fan-in comes first from the configuration's reference module
(``FAN_IN``: leaf name -> a ``ModelConfig`` field or a product of fields),
then from the table below; a leaf in neither is an error.  The reference
reads these arrays by the same names.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "ln_f")


def seed_key(seed: int) -> jax.Array:
    """A key for any whole seed, beyond 32 bits too."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _fan_in(rule: str, name: str, cfg) -> int:
    """``rule``, a product of ``ModelConfig`` fields, evaluated on ``cfg``."""
    n = 1
    for field in rule.split("*"):
        v = getattr(cfg, field.strip(), None)
        if not isinstance(v, int) or isinstance(v, bool):
            raise ValueError(f"fan-in rule {rule!r} of weight {name!r}: "
                             f"{field.strip()!r} is no whole-number field")
        n *= v
    if n <= 0:
        raise ValueError(f"fan-in rule {rule!r} of weight {name!r} gives {n}")
    return n


def _std(name: str, shape, cfg, fan_in: Mapping[str, str]) -> float:
    if name in fan_in:
        return 1.0 / math.sqrt(_fan_in(fan_in[name], name, cfg))
    if name == "wte":
        return 1.0
    if name in ("wq", "wk", "wv", "wg", "wu", "gate", "head"):
        return 1.0 / math.sqrt(cfg.d_model)
    if name == "wo":
        return 1.0 / math.sqrt(cfg.num_heads * cfg.head_dim)
    if name == "wd":
        return 1.0 / math.sqrt(shape[-2])
    raise ValueError(f"no init rule for weight {name!r} {tuple(shape)}")


def make(model, key: jax.Array,
         fan_in: Optional[Mapping[str, str]] = None) -> Dict[str, Any]:
    """Seeded weights in the structure and dtypes of ``model.init``;
    ``fan_in``: the reference module's ``FAN_IN``."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [path[-1].key for path, _ in leaves]
    specs = [leaf for _, leaf in leaves]
    stds = [None if n in NORMS else _std(n, s.shape, model.cfg, fan_in or {})
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
