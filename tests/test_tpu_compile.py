"""Compile, for a described TPU v5e, what the chip runs: the ragged
paged-attention kernel at qwen3-8b decode and 512-row extend geometry,
and the served paged decode step at qwen3-8b's published widths. Nothing
runs — the TPU compiler refuses here what it would refuse on the chip
(unaligned slices, VMEM overruns, programs that do not fit).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import at_depth, get_config
from repro.kernels.paged_attention import ragged_paged_attention
from repro.models import transformer as tfm

QWEN3 = get_config("qwen3-8b")
PAGE = 32
V5E_HBM = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("q_lens,kv_lens", [
    ([1] * 8, [1500, 33, 700, 2047, 1, 64, 999, 128]),   # batched decode
    ([512, 1, 37], [1024, 700, 101]),                    # 512-row extend
], ids=["decode", "extend512"])
def test_paged_attention_kernel_compiles_for_v5e(one_chip, q_lens, kv_lens):
    Hq, Hkv, D = QWEN3.n_heads, QWEN3.n_kv_heads, QWEN3.resolved_head_dim
    S, W = len(q_lens), -(-max(kv_lens) // PAGE)
    P = 1 + sum(-(-k // PAGE) for k in kv_lens)

    def attend(q, kv, tbl, cu, kl):
        return ragged_paged_attention(q, kv, tbl, cu, kl, scale=D ** -0.5,
                                      max_q_len=max(q_lens), backend="pallas",
                                      interpret=False)

    compiled = jax.jit(attend).lower(
        _spec(one_chip, (sum(q_lens), Hq, D), jnp.bfloat16),
        _spec(one_chip, (P, PAGE, 2 * Hkv, D), jnp.bfloat16),
        _spec(one_chip, (S, W), jnp.int32),
        _spec(one_chip, (S + 1,), jnp.int32),
        _spec(one_chip, (S,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_served_decode_step_fits_one_v5e(one_chip):
    """The engine's paged decode step for qwen3-8b at published widths,
    16 of 36 layers, 4 slots over a 17-page pool (what chip_smoke.py
    serves): it compiles and its weights, pool and temporaries fit one
    chip's HBM."""
    cfg = at_depth(QWEN3, 16)
    B, W = 4, 4

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    params = on_chip(tfm.abstract_params(cfg))
    pool = on_chip(jax.eval_shape(
        lambda: tfm.init_paged_caches(cfg, 17, PAGE, jnp.bfloat16)))
    step = jax.jit(lambda p, c, t, pos, tbl, act: tfm.paged_decode(
        cfg, p, c, t, pos, tbl, active=act, page_tokens=PAGE))
    compiled = step.lower(
        params, pool, _spec(one_chip, (B, 1), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), _spec(one_chip, (B, W), jnp.int32),
        _spec(one_chip, (B,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 8 * 10**9      # the weights are real
    assert total < V5E_HBM
