"""Compile, for a described TPU v5e, what the chip runs: the ragged
paged-attention kernel at qwen3-8b decode and 512-row extend geometry,
and the served paged step programs at qwen3-8b's and deepseek-7b's
published widths, as ``ComputeBackend`` builds them on the chip (the
ragged kernel attends) and on the CPU (the rows form attends). Nothing
runs — the TPU compiler refuses here what it would refuse on the chip
(unaligned slices, VMEM overruns, programs that do not fit) — and the
compiled programs show whether the KV pool is updated in place.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import at_depth, get_config
from repro.kernels.paged_attention import ragged_paged_attention
from repro.models import transformer as tfm
from repro.serving.engine import ComputeBackend

QWEN3 = get_config("qwen3-8b")
DEEPSEEK = get_config("deepseek-7b")
PAGE = 32
V5E_HBM = 16 * 10**9
# the benchmark cell's geometry: qwen3-8b at 16 of 36 layers, 32 slots,
# a 961-page pool, page tables 64 wide, 256-token prefill chunks
CELL = dict(layers=16, slots=32, pages=961, width=64, chunk=256)
# deepseek-7b.docqa-decode's: 8 of 30 layers, 16 slots, a 737-page pool,
# tables 128 wide (documents of up to 3.2k tokens)
DOCQA = dict(layers=8, slots=16, pages=737, width=128, chunk=256)


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


@pytest.fixture
def compiled_kernel(monkeypatch, topo):
    """Launch the kernel compiled and with the launch config tuned for
    the described chip, as on the chip: here JAX's platform is the CPU,
    where a launch would otherwise pick the interpreter and the CPU's
    config."""
    from repro.kernels.paged_attention import ops, tune
    kind = topo.devices[0].device_kind.replace(" ", "-").lower()
    monkeypatch.setattr(ops, "resolve_interpret", lambda interpret=None: False)
    monkeypatch.setattr(tune, "_arch", lambda: kind)


def _served_step(sharding, kind: str, layers: int, slots: int, pages: int,
                 width: int, chunk: int = 0, model=QWEN3,
                 attn_kernel: bool = False):
    """The engine's paged step program of ``kind`` for ``model`` at
    published widths and ``layers`` layers, compiled for the chip, with
    the rows form or the ragged kernel attending. Returns (compiled,
    cfg)."""
    cfg = at_depth(model, layers)

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), tree)

    params = on_chip(tfm.abstract_params(cfg))
    pool = on_chip(jax.eval_shape(
        lambda: tfm.init_paged_caches(cfg, pages, PAGE, jnp.bfloat16)))
    step = ComputeBackend.paged_program(cfg, kind, PAGE, attn_kernel)
    if kind == "decode":
        args = (_spec(sharding, (slots, 1), jnp.int32),
                _spec(sharding, (slots,), jnp.int32),
                _spec(sharding, (slots, width), jnp.int32),
                _spec(sharding, (slots,), jnp.bool_))
    else:
        args = (_spec(sharding, (1, chunk), jnp.int32),
                _spec(sharding, (), jnp.int32),
                _spec(sharding, (1, width), jnp.int32))
    return step.lower(params, pool, *args).compile(), cfg


@pytest.mark.parametrize("kind,model,cell,attn_kernel", [
    ("decode", QWEN3, CELL, False), ("extend", QWEN3, CELL, False),
    ("decode", QWEN3, CELL, True), ("extend", QWEN3, CELL, True),
    ("decode", DEEPSEEK, DOCQA, True),
], ids=["decode", "extend", "decode-kernel", "extend-kernel",
        "decode-kernel-deepseek"])
def test_served_step_updates_the_pool_in_place(one_chip, compiled_kernel,
                                               kind, model, cell,
                                               attn_kernel):
    """At the benchmark cells' geometry the served decode step and the
    256-token extend step own the pool: it is aliased to the output, the
    temporaries stay under one layer's pool, and no slice, write-back or
    copy of a layer's pool or of the whole pool is left in the program.
    On the kernel path the attention is the ragged kernel, reading the
    stacked pool in place (2*Hkv = 16 for qwen3-8b, 64 for deepseek-7b)."""
    compiled, cfg = _served_step(one_chip, kind, model=model,
                                 attn_kernel=attn_kernel, **cell)
    layer_pool = (cell["pages"], PAGE, 2 * cfg.n_kv_heads,
                  cfg.resolved_head_dim)
    pool_bytes = cell["layers"] * 2 * int(jnp.prod(jnp.asarray(layer_pool)))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // cell["layers"]
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) is attn_kernel
    dims = ",".join(map(str, layer_pool))
    pool_shape = re.compile(rf"bf16\[({cell['layers']},|1,)?{dims}\]")
    moves = re.compile(r"^\s*(?:ROOT )?%\S+ = (\S+) "
                       r"(dynamic-slice|dynamic-update-slice|copy)\(")
    found = [m.group(0) for m in map(moves.match, text.splitlines())
             if m and pool_shape.match(m.group(1))]
    assert not found, found


def test_served_decode_step_fits_one_v5e(one_chip, compiled_kernel):
    """The engine's paged decode step for qwen3-8b at published widths,
    16 of 36 layers, 4 slots over a 17-page pool (what chip_smoke.py
    serves), attending in the rows form and through the ragged kernel:
    it compiles and its weights, pool and temporaries fit one chip's
    HBM."""
    for attn_kernel in (False, True):
        compiled, _ = _served_step(one_chip, "decode", layers=16, slots=4,
                                   pages=17, width=4,
                                   attn_kernel=attn_kernel)
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
        assert mem.argument_size_in_bytes > 8 * 10**9  # the weights are real
        assert total < V5E_HBM
        assert ("tpu_custom_call" in compiled.as_text()) is attn_kernel
