"""The paged step programs own the KV pool (DESIGN.md §10): each unit's
pool rides through the layer scan in its carry and is donated to the
program, which updates it in place. For every paged family, a donated
extend chunk and decode round write only their own rows, and give the
logits and pools of the same calls on pools that are not donated; a pool
that other code still holds is copied before a launch, not consumed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels.paged_attention import (paged_attention_rows,
                                           write_tokens_to_pages)
from repro.models import init_params
from repro.serving import EngineConfig
from repro.serving.engine import ComputeBackend, PrefillChunk

FAMILIES = {"attention": "qwen3-8b", "mla": "deepseek-v2-lite-16b",
            "ssm": "mamba2-2.7b", "hybrid": "hymba-1.5b"}
PT, W = 16, 4


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    cfg = reduced(get_config(FAMILIES[request.param]))
    return cfg, init_params(cfg, jax.random.key(0))


def _undonated(cfg, kind, page_tokens, attn_kernel=False):
    """The backend's step program, jitted without donating the pool."""
    return jax.jit(ComputeBackend.paged_program(
        cfg, kind, page_tokens, attn_kernel).__wrapped__)


def _backend(cfg, params, donate=True):
    """A paged backend whose sampled logits are kept, in order."""
    ecfg = EngineConfig(max_slots=2, max_cache_len=64, page_tokens=PT,
                        weight_tier="hbm", kv_tier="mrm")
    be = ComputeBackend(cfg, params, ecfg)
    if not donate:
        be.paged_program = _undonated
    logits, sample = [], be.sample

    def keep(x):
        logits.append(np.asarray(x))
        return sample(x)
    be.sample = keep
    return be, logits


def _host(be) -> dict:
    """Host copy of every pool leaf, by its path in the pool tree (a
    copy: a zero-copy view would hold the leaf, and the next launch
    would then copy the pool rather than consume it)."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(be.paged_caches)
    return {jax.tree_util.keystr(p): np.array(a) for p, a in leaves}


def _only_written(before: dict, after: dict, page: int, rows) -> None:
    """``after`` equals ``before`` outside (page, rows) of every layer's
    KV pages and outside ``page`` of every state-page leaf, and the
    call wrote there."""
    for k, b in before.items():
        a, expect = after[k], b.copy()
        at = ((slice(None), page, rows) if "kv_pages" in k
              else (slice(None), page))
        expect[at] = a[at]
        assert np.array_equal(expect, a, equal_nan=True), k
        assert not np.array_equal(b[at], a[at]), k


def _serve(be, pages):
    """A first chunk filling page 0, an extend chunk filling page 1, and
    one decode round writing row 0 of page 2 — each chunk within one
    page, as the engine chunks point stacks. Checks the rows each
    donated launch wrote. Returns the decode round's tokens."""
    table = np.zeros((W,), np.int32)
    table[:len(pages)] = pages
    m = be.prefix_len()
    be.run_prefill_chunk(PrefillChunk(
        slot=0, request_id=0, tokens=np.arange(2, 2 + PT - m),
        offset=m, first=True, last=False), page_table=table)
    before = _host(be)
    be.run_prefill_chunk(PrefillChunk(
        slot=0, request_id=0, tokens=np.arange(40, 40 + PT), offset=PT,
        first=False, last=True), page_table=table)
    after = _host(be)
    _only_written(before, after, pages[1], slice(0, PT))
    tables = np.zeros((2, W), np.int32)
    tables[0] = table
    tok = be.run_decode([0], page_tables=tables)
    _only_written(after, _host(be), pages[2], 0)
    return tok


def test_donated_steps_write_only_their_rows_and_match_undonated(family):
    cfg, params = family
    runs = []
    for donate in (True, False):
        be, logits = _backend(cfg, params, donate)
        pages = [be.alloc_page() for _ in range(3)]
        tok = _serve(be, pages)
        runs.append((be, logits, tok))
    (be, logits, tok), (ref, ref_logits, ref_tok) = runs
    assert len(logits) == len(ref_logits) == 2
    for x, y in zip(logits, ref_logits):
        assert np.array_equal(x, y)
    assert np.array_equal(tok, ref_tok)
    got, want = _host(be), _host(ref)
    assert all(np.array_equal(got[k], want[k], equal_nan=True) for k in want)
    assert be.pool_copies == 0
    assert ref.pool_copies == 3       # every launch left its input pool


def test_a_held_pool_is_copied_not_consumed():
    """Code that keeps the pool across a launch keeps a live, unchanged
    pool; the launch copies it first and counts the copy."""
    cfg = reduced(get_config("qwen3-8b"))
    be, _ = _backend(cfg, init_params(cfg, jax.random.key(0)))
    pages = [be.alloc_page() for _ in range(3)]
    table = np.zeros((W,), np.int32)
    table[:3] = pages
    held = be.paged_caches
    before = _host(be)
    be.run_prefill_chunk(PrefillChunk(
        slot=0, request_id=0, tokens=np.arange(2, 2 + PT), offset=0,
        first=True, last=True), page_table=table)
    assert be.pool_copies == 1
    for k, a in zip(before, jax.tree.leaves(held)):
        assert not a.is_deleted()
        assert np.array_equal(np.array(a), before[k])
    del held, a
    tables = np.zeros((2, W), np.int32)
    tables[0] = table
    be.run_decode([0], page_tables=tables)
    assert be.pool_copies == 1


def test_layer_index_reads_and_writes_one_layer_of_a_stacked_pool():
    """With a layer index, the page write and the page attention act on
    that layer of a pool stacked over layers exactly as they act on the
    layer's own pool."""
    L, P, ps, hkv, D, B, S = 3, 7, 4, 2, 8, 2, 3
    ks = jax.random.split(jax.random.key(1), 3)
    pool = jax.random.normal(ks[0], (L, P, ps, 2 * hkv, D), jnp.float32)
    kv_new = jax.random.normal(ks[1], (B, S, 2 * hkv, D), jnp.float32)
    q = jax.random.normal(ks[2], (B * S, 2 * hkv, D), jnp.float32)
    table = jnp.asarray([[1, 3, 0], [2, 5, 6]], jnp.int32)
    pos = jnp.asarray([[2, 3, 4], [5, 6, 7]], jnp.int32)
    active = jnp.asarray([True, False])
    for layer in range(L):
        one = write_tokens_to_pages(pool[layer], kv_new, pos, table,
                                    active=active)
        stacked = write_tokens_to_pages(pool, kv_new, pos, table,
                                        active=active, layer=layer)
        assert np.array_equal(stacked, pool.at[layer].set(one))
        rows_tbl, q_pos = jnp.repeat(table, S, axis=0), pos.reshape(-1)
        assert np.array_equal(
            paged_attention_rows(q, stacked, rows_tbl, q_pos, scale=0.3,
                                 layer=layer),
            paged_attention_rows(q, one, rows_tbl, q_pos, scale=0.3))
