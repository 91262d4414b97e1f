"""Compile-only checks of the serving kernel for a described TPU v5e chip.

``cache_moe`` (the slot-indexed expert FFN of every verify step) is
compiled by the TPU compiler at Mixtral-8x7B's and DeepSeek-V2-Lite's
published widths in bfloat16, for a chip that is described and not
attached: what the chip's
compiler would refuse (misaligned blocks, too much fast memory, a program
that does not fit) fails here without a chip.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.cache_moe import cache_moe

POOL = 8                  # cache slots; <= T*k, so no slot compaction


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.mark.parametrize("tokens", [5, 10], ids=["solo-block", "fused-round"])
def test_cache_moe_compiles_for_v5e(one_chip, tokens):
    """A solo sd block (draft_len 4 -> T=5) and a fused round of two
    (T=10), top-2 routing over an 8-slot pool of full-width experts."""
    cfg = get_config("mixtral-8x7b")
    d, f, k = cfg.d_model, cfg.moe_d_ff, cfg.num_experts_per_tok
    bf16 = jnp.dtype(cfg.dtype)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(cache_moe).lower(
        arg((tokens, d), bf16), arg((tokens, k), jnp.int32),
        arg((tokens, k), bf16), arg((POOL, d, f), bf16),
        arg((POOL, f, d), bf16), arg((POOL, d, f), bf16)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # pool <= T*k: no slot compaction, so no expert weights are copied
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 26


@pytest.mark.parametrize("tokens,temp_mb", [(5, 1200), (512, 640)],
                         ids=["decode-block", "prefill-block"])
def test_cache_moe_compiles_for_v5e_at_deepseek_widths(one_chip, tokens,
                                                       temp_mb):
    """DeepSeek-V2-Lite's experts (f = 1408 = 11 x 128, one block along f)
    top-6 over a 128-slot pool: a decode block (T=5, compacted to T*k = 30
    slots, whose weight rows are gathered: 30 x 17.3 MB) and a 512-token
    prefill block (per-slot capacity T, not T*k)."""
    cfg = get_config("deepseek-v2-lite-16b")
    d, f, k = cfg.d_model, cfg.moe_d_ff, cfg.num_experts_per_tok
    bf16 = jnp.dtype(cfg.dtype)
    pool = 128

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(cache_moe).lower(
        arg((tokens, d), bf16), arg((tokens, k), jnp.int32),
        arg((tokens, k), bf16), arg((pool, d, f), bf16),
        arg((pool, f, d), bf16), arg((pool, d, f), bf16)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mb * 2 ** 20
