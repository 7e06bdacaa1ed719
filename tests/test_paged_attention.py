"""Ragged paged-attention kernel (DESIGN.md §10): interpret-mode Pallas
parity against the jnp reference across page sizes / ragged batches /
layer features, bit-invariance to table padding, and the serving-level
paged-plane guarantees — a prefix hit decodes bit-identically (fp32) to
a cold start with ZERO copy bytes, and the per-tier metered reads equal
the kernel's page-gather byte count."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.memclass import HBM3E, MRM_RRAM
from repro.core.simulator import MemorySystem
from repro.kernels.paged_attention import (interleave_kv,
                                           ragged_paged_attention,
                                           ragged_paged_attention_ref)
from repro.models import init_params
from repro.serving import EngineConfig, ServeEngine

RNG = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# Kernel: Pallas (interpret) vs jnp reference
# ---------------------------------------------------------------------------


def _case(q_lens, kv_lens, ps, Hq, Hkv, D, extra_pages=0, dtype=jnp.float32):
    """Random ragged batch on paged storage. Every sequence gets its own
    page run; page 0 stays the reserved null page. ``extra_pages`` pads
    each table row with trailing null slots (must not change results)."""
    S = len(q_lens)
    W = max(-(-k // ps) for k in kv_lens)
    P = 1 + S * W
    kv_pages = jnp.asarray(RNG.normal(0, 1, (P, ps, 2 * Hkv, D)), dtype)
    table = np.zeros((S, W + extra_pages), np.int32)
    for s, klen in enumerate(kv_lens):
        n = -(-klen // ps)
        table[s, :n] = 1 + s * W + np.arange(n)
    T = sum(q_lens)
    q = jnp.asarray(RNG.normal(0, 1, (T, Hq, D)), dtype)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
    return (q, kv_pages, jnp.asarray(table), cu,
            jnp.asarray(kv_lens, jnp.int32))


@pytest.mark.parametrize("ps", [8, 16, 32])
@pytest.mark.parametrize("q_lens,kv_lens", [
    ([5, 1, 9], [37, 12, 9]),          # mixed extend + decode, ragged
    ([1, 1, 1, 1], [33, 7, 64, 17]),   # pure batched decode
    ([16], [48]),                      # single chunked-extend sequence
])
@pytest.mark.parametrize("cap,window", [(None, None), (30.0, None),
                                        (None, 20), (30.0, 20)])
def test_pallas_matches_reference(ps, q_lens, kv_lens, cap, window):
    q, kvp, tbl, cu, kl = _case(q_lens, kv_lens, ps, Hq=4, Hkv=2, D=16)
    scale = 16 ** -0.5
    out = ragged_paged_attention(q, kvp, tbl, cu, kl, scale=scale, cap=cap,
                                 window=window, max_q_len=max(q_lens),
                                 backend="pallas", interpret=True)
    ref = ragged_paged_attention_ref(q, kvp, tbl, cu, kl, scale=scale,
                                     cap=cap, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-6, rtol=3e-6)


def test_pallas_bit_exact_fp32():
    """With one page per block (``block_kv=1``) the fp32 interpret-mode
    lowering reduces in the same page order as the reference scan —
    outputs are bit-identical. Wider blocks sum a block's pages at once
    (``test_pallas_matches_reference``)."""
    q, kvp, tbl, cu, kl = _case([5, 1, 9], [37, 12, 9], 16, 4, 2, 16)
    out = ragged_paged_attention(q, kvp, tbl, cu, kl, scale=0.25,
                                 max_q_len=9, backend="pallas",
                                 block_kv=1, interpret=True)
    ref = ragged_paged_attention_ref(q, kvp, tbl, cu, kl, scale=0.25)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_table_padding_bit_invariance():
    """Trailing null-page table slots contribute exp-weight 0 and
    correction 1 to the online softmax — results are BIT-identical, so a
    borrower whose table is wider than the donor's never diverges."""
    args = dict(q_lens=[4, 7], kv_lens=[29, 18], ps=8, Hq=2, Hkv=1, D=8)
    q, kvp, tbl0, cu, kl = _case(**args)
    q2, kvp2, tbl4, _, _ = _case(extra_pages=4, **args)
    out0 = ragged_paged_attention_ref(q, kvp, tbl0, cu, kl, scale=0.3)
    out4 = ragged_paged_attention_ref(q, kvp, tbl4, cu, kl, scale=0.3)
    assert np.array_equal(np.asarray(out0), np.asarray(out4))
    p0 = ragged_paged_attention(q, kvp, tbl0, cu, kl, scale=0.3,
                                max_q_len=7, backend="pallas",
                                interpret=True)
    p4 = ragged_paged_attention(q, kvp, tbl4, cu, kl, scale=0.3,
                                max_q_len=7, backend="pallas",
                                interpret=True)
    assert np.array_equal(np.asarray(p0), np.asarray(p4))


@pytest.mark.parametrize("ps", [8, 16, 32])
@pytest.mark.parametrize("q_lens,kv_lens", [
    ([5, 1, 9], [37, 12, 9]),          # mixed extend + decode, ragged
    ([1, 1, 1, 1], [33, 7, 64, 17]),   # pure batched decode
])
def test_grouped_grid_sparse_table_parity(ps, q_lens, kv_lens):
    """Sparse page tables (interior null slots): the grouped grid skips
    null pages without a gather, the ungrouped baseline pulls and masks
    them in-register, the reference masks by page id. With one page per
    block both grids reduce page by page, so they agree bit-for-bit in
    fp32 whatever the query tile and pipeline depth. Wider blocks agree
    bit-for-bit across query tiles and depths at the same block width.
    Against the reference every grid agrees to a few ulp: the kernel's
    score and value dots may sum their terms in another order than the
    reference's page scan, and a block sums its pages at once, which
    moves the fp32 result by rounding only. The host-side gather replica
    confirms the grouped grid reads strictly fewer pages."""
    from repro.kernels.paged_attention.kernel import pages_gathered
    q, kvp, tbl, cu, kl = _case(q_lens, kv_lens, ps, Hq=4, Hkv=2, D=16)
    tbl = np.array(tbl)
    tbl[:, 1::2] = 0                    # null out every other slot
    tbl = jnp.asarray(tbl)
    kw = dict(scale=16 ** -0.5, max_q_len=max(q_lens))
    ref = np.asarray(ragged_paged_attention_ref(q, kvp, tbl, cu, kl,
                                                scale=kw["scale"]))
    # four ulp of the largest output: outputs are convex combinations of
    # value rows, so rounding errors scale with the largest magnitude
    atol = 4 * float(np.spacing(np.abs(ref).max()))
    base = np.asarray(ragged_paged_attention(
        q, kvp, tbl, cu, kl, backend="pallas", interpret=True,
        skip_blocks=False, block_kv=1, **kw))
    np.testing.assert_allclose(base, ref, rtol=0, atol=atol)

    def grouped(bq, bkv, nb):
        return np.asarray(ragged_paged_attention(
            q, kvp, tbl, cu, kl, backend="pallas", interpret=True,
            block_q=bq, block_kv=bkv, num_buffers=nb, **kw))

    for bq, nb in [(4, 2), (8, 3), (64, 4)]:
        assert np.array_equal(grouped(bq, 1, nb), base)
    for bq, bkv, nb in [(4, 2, 2), (8, 4, 3), (64, 64, 4)]:
        out = grouped(bq, bkv, nb)
        np.testing.assert_allclose(out, ref, rtol=0, atol=atol)
        assert np.array_equal(grouped(2 * bq, bkv, 6 - nb), out)
    grouped = pages_gathered(tbl, cu, kl, page_size=ps,
                             max_q_len=kw["max_q_len"])
    full = pages_gathered(tbl, cu, kl, page_size=ps,
                          max_q_len=kw["max_q_len"], skip_blocks=False)
    assert 0 < grouped < full


def test_kernel_config_resolution():
    """Explicit block/buffer overrides win over the tuned cache; the
    query tile is clamped to the launch's rows and the depth to 2-4, and
    the page block is left as it is whatever the table width."""
    from repro.kernels.paged_attention.tune import (KernelConfig,
                                                    best_config,
                                                    resolve_config,
                                                    set_config)
    set_config(64, 48, KernelConfig(block_q=32, block_kv=16, num_buffers=4))
    assert best_config(64, 48) == KernelConfig(32, 16, 4)
    eff = resolve_config(64, 48, max_q_len=5)
    assert eff == KernelConfig(block_q=5, block_kv=16, num_buffers=4)
    eff = resolve_config(64, 48, max_q_len=100,
                         block_q=8, block_kv=2, num_buffers=9)
    assert eff == KernelConfig(block_q=8, block_kv=2, num_buffers=4)


def test_interpret_mode_follows_the_platform():
    """One function decides interpret mode: on only on the CPU backend,
    and an explicit value always wins."""
    from repro.kernels import resolve_interpret
    assert resolve_interpret() is (jax.default_backend() == "cpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


@pytest.mark.parametrize("Hkv,D", [(5, 64), (3, 128), (1, 576), (8, 64)])
def test_compiled_kernel_refuses_unaligned_geometry(Hkv, D):
    """Page geometries the TPU compiler refuses (fused head axis not 2, 4
    or a multiple of 8; head dim not a multiple of 128) raise a named
    error before lowering instead of failing inside Mosaic; interpret
    mode still takes them."""
    from repro.kernels.paged_attention import KernelGeometryError
    q, kvp, tbl, cu, kl = _case([1, 2], [9, 20], 8, Hq=2 * Hkv, Hkv=Hkv, D=D)
    with pytest.raises(KernelGeometryError, match="fused K/V heads"):
        ragged_paged_attention(q, kvp, tbl, cu, kl, scale=0.1, max_q_len=2,
                               backend="pallas", interpret=False)
    out = ragged_paged_attention(q, kvp, tbl, cu, kl, scale=0.1, max_q_len=2,
                                 backend="pallas", interpret=True)
    assert out.shape == q.shape


def test_explicit_positions_ring_layout():
    """The q_pos/kv_pos_pages variant (ring-cache compatibility: the
    decode_attention wrapper) masks by stored positions, not slot-derived
    ones — scattered/empty rows behave like the legacy kernel."""
    ps, Hkv, D = 16, 2, 16
    B, C = 2, 48
    k = RNG.normal(0, 1, (B, C, Hkv, D))
    v = RNG.normal(0, 1, (B, C, Hkv, D))
    pos = np.where(RNG.random((B, C)) < 0.8,
                   RNG.integers(0, 40, (B, C)), -1).astype(np.int32)
    cur = np.asarray([25, 37], np.int32)
    kvp = jnp.asarray(np.stack([k, v], axis=3).reshape(B, C, 2 * Hkv, D)
                      .reshape(B * C // ps, ps, 2 * Hkv, D), jnp.float32)
    kv_pos = jnp.asarray(pos.reshape(B * C // ps, ps))
    n_per = C // ps
    tbl = jnp.arange(B * n_per, dtype=jnp.int32).reshape(B, n_per)
    q = jnp.asarray(RNG.normal(0, 1, (B, 4, D)), jnp.float32)
    cu = jnp.arange(B + 1, dtype=jnp.int32)
    kl = jnp.full((B,), C, jnp.int32)
    out = ragged_paged_attention(q, kvp, tbl, cu, kl, scale=0.25,
                                 q_pos=jnp.asarray(cur), kv_pos_pages=kv_pos,
                                 max_q_len=1, backend="pallas",
                                 interpret=True)
    ref = ragged_paged_attention_ref(q, kvp, tbl, cu, kl, scale=0.25,
                                     q_pos=jnp.asarray(cur),
                                     kv_pos_pages=kv_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-6, rtol=3e-6)


def test_interleave_layout_roundtrip():
    k = jnp.asarray(RNG.normal(0, 1, (2, 5, 3, 4)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (2, 5, 3, 4)), jnp.float32)
    kv = interleave_kv(k, v)
    assert kv.shape == (2, 5, 6, 4)
    # K heads at even fused indices, V heads at odd — the layout every
    # page gather in the kernel assumes
    assert np.array_equal(np.asarray(kv[:, :, 0::2]), np.asarray(k))
    assert np.array_equal(np.asarray(kv[:, :, 1::2]), np.asarray(v))


# ---------------------------------------------------------------------------
# Serving: zero-copy prefix hits on the paged plane
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=["deepseek-7b", "deepseek-v2-lite-16b"])
def arch_setup(request):
    full = get_config(request.param)
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    return full, cfg, params


def _mk_engine(full, cfg, params, **kw):
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    ecfg = dict(max_slots=2, max_cache_len=96, weight_tier="hbm",
                kv_tier="mrm", eos_token=-1, chunk_tokens=16, page_tokens=16,
                radix_hot_threshold=2)
    ecfg.update(kw)
    return ServeEngine(cfg, params, mem, EngineConfig(**ecfg),
                       account_cfg=full)


def _outputs(eng):
    return {k: list(v) for k, v in eng.outputs.items()}


def _run(eng, prompts, max_new=6):
    for p in prompts:   # sequential: each later prompt can hit
        eng.submit(list(p), max_new)
        eng.run_until_idle()
    return eng.report()


def test_paged_hit_bit_equal_zero_copy_metered(arch_setup):
    """Per positional family (GQA + MLA): prefix-hit decode on the
    paged plane is bit-identical (fp32) to a cold start, and the KV
    tier's read stream equals the kernel's analytically-metered page
    gathers exactly."""
    full, cfg, params = arch_setup
    rng = np.random.default_rng(5)
    base = rng.integers(2, 400, 40)
    prompts = [base, np.concatenate([base[:32], rng.integers(2, 400, 9)])]

    paged = _mk_engine(full, cfg, params)
    rep = _run(paged, prompts)
    cold = _mk_engine(full, cfg, params, prefix_caching=False)
    _run(cold, prompts)

    assert _outputs(paged) == _outputs(cold)
    assert rep["prefix"]["compute_hits"] >= 1
    # metering: the KV tier read exactly what the kernel gathered (plus
    # the read half of any sub-page tail copy)
    assert rep["kernel_read_bytes"] > 0
    mrm_reads = paged.mem.devices["mrm"].stats.read_bytes
    assert mrm_reads == pytest.approx(
        rep["kernel_read_bytes"] + paged.kv.tail_copy_bytes / 2)


def test_paged_subpage_tail_bit_equal():
    """Sub-page tail reuse on the paged plane: the ONLY bytes a hit ever
    copies are the tail rows of one page (copy_page_rows), and outputs
    stay bit-identical to a cold run."""
    full = get_config("deepseek-7b")
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(41)
    head = list(rng.integers(2, 400, 55))   # straddles a 16-token page
    prompts = [head + list(rng.integers(2, 400, 9)) for _ in range(3)]

    paged = _mk_engine(full, cfg, params)
    _run(paged, prompts)
    cold = _mk_engine(full, cfg, params, prefix_caching=False)
    _run(cold, prompts)

    assert _outputs(paged) == _outputs(cold)
    assert paged.kv.tail_hits > 0
    assert paged.prefill_tokens_computed < cold.prefill_tokens_computed


def test_paged_migration_splices_pages(arch_setup):
    """Cross-replica migration on the paged plane ships page data: the
    receiver writes the donor's compute pages into its pool, and a local
    hit on the grafted prefix decodes identically to the donor."""
    full, cfg, params = arch_setup
    rng = np.random.default_rng(11)
    p = rng.integers(2, 400, 36)
    donor = _mk_engine(full, cfg, params)
    _run(donor, [p], max_new=4)
    recv = _mk_engine(full, cfg, params)

    key = donor.radix_key_for(list(p))
    exp = donor.export_prefix(key)
    assert exp is not None and exp.get("page_data") is not None
    imp = recv.import_prefix(exp["tokens"], hot=exp["hot"],
                             hits=exp["hits"], page_data=exp["page_data"],
                             page_tokens=exp["page_tokens"])
    assert imp["total_tokens"] > 0

    rep = _run(recv, [p], max_new=4)
    d_out, r_out = list(donor.outputs[0]), list(recv.outputs[0])
    assert d_out == r_out
    assert rep["prefix"]["compute_hits"] == 1

    # geometry mismatch is rejected BEFORE adoption (a graft this engine
    # cannot compute on would poison later hits)
    recv2 = _mk_engine(full, cfg, params)
    bad = recv2.import_prefix(exp["tokens"], page_data=exp["page_data"],
                              page_tokens=exp["page_tokens"] * 2)
    assert bad["total_tokens"] == 0
    assert recv2.kv.radix.match(key, recv2.mem.now).tokens == 0


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "hymba-1.5b"])
def test_paged_point_stack_hit_bit_equal(arch):
    """The paged plane is universal: SSM and hybrid stacks serve on
    pooled point-state pages (conv + recurrent state captured at page
    boundaries), and a prefix hit resumes from a sealed page's state
    bit-identically (fp32) to a cold start."""
    full = get_config(arch)
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(13)
    base = rng.integers(2, 400, 40)
    prompts = [base, np.concatenate([base[:32], rng.integers(2, 400, 9)])]

    paged = _mk_engine(full, cfg, params)
    rep = _run(paged, prompts)
    cold = _mk_engine(full, cfg, params, prefix_caching=False)
    _run(cold, prompts)

    assert _outputs(paged) == _outputs(cold)
    assert rep["prefix"]["compute_hits"] >= 1
    # recurrent-state pages ride the same accounting: page reads carry
    # the per-page state snapshot bytes, and the KV tier read exactly
    # what the step programs gathered (point stacks copy no tails)
    assert paged.kv.state_bytes_page > 0
    assert rep["kernel_read_bytes"] > 0 and paged.kv.tail_copy_bytes == 0
    assert paged.mem.devices["mrm"].stats.read_bytes == pytest.approx(
        rep["kernel_read_bytes"])
    assert paged.prefill_tokens_computed < cold.prefill_tokens_computed


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "hymba-1.5b"])
def test_paged_state_page_migration(arch):
    """Cross-replica migration of point-state pages: the receiver grafts
    conv/state pages and a local hit decodes identically to the donor;
    wrong page geometry or mangled state leaves are rejected BEFORE
    adoption."""
    full = get_config(arch)
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(17)
    p = rng.integers(2, 400, 32)
    donor = _mk_engine(full, cfg, params)
    _run(donor, [p], max_new=4)

    key = donor.radix_key_for(list(p))
    exp = donor.export_prefix(key)
    assert exp is not None and exp.get("page_data") is not None

    recv = _mk_engine(full, cfg, params)
    imp = recv.import_prefix(exp["tokens"], hot=exp["hot"],
                             hits=exp["hits"], page_data=exp["page_data"],
                             page_tokens=exp["page_tokens"])
    assert imp["total_tokens"] > 0
    rep = _run(recv, [p], max_new=4)
    assert list(donor.outputs[0]) == list(recv.outputs[0])
    assert rep["prefix"]["compute_hits"] == 1

    # page-size mismatch: state captured at foreign page boundaries is
    # meaningless here
    recv2 = _mk_engine(full, cfg, params)
    bad = recv2.import_prefix(exp["tokens"], page_data=exp["page_data"],
                              page_tokens=exp["page_tokens"] * 2)
    assert bad["total_tokens"] == 0
    assert recv2.kv.radix.match(key, recv2.mem.now).tokens == 0
    # mangled state-page leaves (wrong recurrent-state geometry)
    mangled = jax.tree.map(lambda a: a[..., :-1], exp["page_data"])
    bad = recv2.import_prefix(exp["tokens"], page_data=mangled,
                              page_tokens=exp["page_tokens"])
    assert bad["total_tokens"] == 0
    assert recv2.kv.radix.match(key, recv2.mem.now).tokens == 0


def test_paged_pool_growth_and_row_copy():
    """The compute-page pool doubles when the free list drains (every
    cache-family leaf widens on the page axis) and copy_page_rows moves
    exactly the requested rows."""
    from repro.serving.engine import ComputeBackend
    full = get_config("deepseek-7b")
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    ecfg = EngineConfig(max_slots=2, max_cache_len=96, page_tokens=16,
                        weight_tier="hbm", kv_tier="mrm")
    b = ComputeBackend(cfg, params, ecfg)
    pool0 = jax.tree.leaves(b.paged_caches)[0].shape[1]
    ids = [b.alloc_page() for _ in range(pool0 + 3)]   # forces a doubling
    assert len(set(ids)) == len(ids) and 0 not in ids
    pool1 = jax.tree.leaves(b.paged_caches)[0].shape[1]
    assert pool1 == 2 * pool0
    # mark page ids[0], copy 5 rows into ids[1]
    b.paged_caches = jax.tree.map(
        lambda a: a.at[:, ids[0]].set(1.0), b.paged_caches)
    b.copy_page_rows(ids[0], ids[1], 5)
    for leaf in jax.tree.leaves(b.paged_caches):
        got = np.asarray(leaf[:, ids[1]])
        assert np.all(got[:, :5] == 1.0) and np.all(got[:, 5:] == 0.0)
    for pid in ids:
        b.free_page(pid)
    assert len(b._free) == pool1 - 1
