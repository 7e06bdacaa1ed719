"""The paged attention as the chip serves it (DESIGN.md §10): the paged
branch of ``attention_sublayer`` attending through the ragged kernel
(``attn_kernel``, in interpret mode here) against the rows form that the
CPU serves, for decode rounds with idle rows, extend chunks resuming
mid-page and first chunks, grouped and multi-head; the kernel reading one
layer of a pool stacked over layers in place; and which path the backend
picks for which device and page geometry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels.paged_attention import (ops, ragged_paged_attention,
                                           ragged_paged_attention_ref)
from repro.kernels.paged_attention.tune import resolve_config
from repro.models import init_params
from repro.models.attention import attention_sublayer
from repro.serving.engine import ComputeBackend

PS, W, L, P = 16, 8, 3, 40
# reduced widths at the served head dim: G = 4 (qwen3-8b's grouping) and
# G = 1 (deepseek-7b's multi-head attention)
GEOMETRY = {"G4": ("qwen3-8b", dict(n_heads=8, n_kv_heads=2)),
            "G1": ("deepseek-7b", dict(n_heads=4, n_kv_heads=4))}


@pytest.fixture(scope="module", params=list(GEOMETRY))
def layer_setup(request):
    name, heads = GEOMETRY[request.param]
    cfg = reduced(get_config(name), head_dim=128, dtype="float32",
                  param_dtype="float32", **heads)
    params = init_params(cfg, jax.random.key(3))
    # the second layer's attention weights of the first scan group
    p = jax.tree.map(lambda a: a[1], params["groups"][0][0]["mixer"])
    rng = np.random.default_rng(5)
    pool = rng.normal(0, 1, (L, P, PS, 2 * cfg.n_kv_heads, 128))
    pool[:, 0] = 0.0                                  # the null page
    return cfg, p, jnp.asarray(pool, jnp.float32), rng


def _close(kern, rows):
    """fp32 agreement at 1e-5 of the output's scale: the kernel sums a
    block of pages at once where the rows form goes page by page."""
    np.testing.assert_allclose(kern, rows, rtol=1e-5,
                               atol=1e-5 * np.abs(rows).max())


def _both(cfg, p, pool, x, **kw):
    """The sublayer on the rows form and on the kernel, at layer 1."""
    outs = [attention_sublayer(cfg, p, x, window=None,
                               cache={"kv_pages": pool}, layer=jnp.int32(1),
                               attn_kernel=k, **kw) for k in (False, True)]
    (o_rows, c_rows), (o_kern, c_kern) = outs
    assert np.array_equal(np.asarray(c_rows["kv_pages"]),
                          np.asarray(c_kern["kv_pages"]))
    return np.asarray(o_rows), np.asarray(o_kern)


def test_decode_round_with_idle_rows(layer_setup):
    """Four decode rows over a padded 8-slot table: two carry sequences
    whose next token lands mid-page (positions 37 and 20), two are idle
    with all-null tables. The idle rows come back zero."""
    cfg, p, pool, rng = layer_setup
    tbl = np.zeros((4, W), np.int32)
    tbl[0, :3] = [5, 9, 2]                            # keys 0..37
    tbl[2, :2] = [11, 7]                              # keys 0..20
    cur = jnp.asarray([37, 0, 20, 0], jnp.int32)
    act = jnp.asarray([True, False, True, False])
    x = jnp.asarray(rng.normal(0, 1, (4, 1, cfg.d_model)), jnp.float32)
    rows, kern = _both(cfg, p, pool, x, positions=cur[:, None],
                       mode="decode", cur_pos=cur, decode_active=act,
                       page_table=jnp.asarray(tbl))
    _close(kern, rows)
    assert not kern[[1, 3]].any()


@pytest.mark.parametrize("offset,length", [(37, 21), (0, 24)],
                         ids=["extend-mid-page", "first-chunk"])
def test_prompt_chunk(layer_setup, offset, length):
    """An extend chunk of 21 rows (not a whole number of 16-row query
    tiles) resuming at position 37, mid-page; and a first chunk."""
    cfg, p, pool, rng = layer_setup
    tbl = np.zeros((1, W), np.int32)
    n = -(-(offset + length) // PS)
    tbl[0, :n] = [13, 4, 21, 8][:n]
    x = jnp.asarray(rng.normal(0, 1, (1, length, cfg.d_model)), jnp.float32)
    pos = offset + jnp.arange(length, dtype=jnp.int32)
    rows, kern = _both(cfg, p, pool, x, positions=pos, mode="extend",
                       page_table=jnp.asarray(tbl))
    _close(kern, rows)


def test_layer_indexed_kernel_reads_that_layer_in_place():
    """The kernel on a pool stacked over layers, given a layer index,
    gives bit for bit what it gives on that layer's pool sliced out; and
    the reference agrees to fp32 rounding."""
    rng = np.random.default_rng(9)
    pool = jnp.asarray(rng.normal(0, 1, (3, 12, 8, 4, 128)), jnp.float32)
    tbl = jnp.asarray([[3, 0, 7, 1], [5, 2, 0, 0]], jnp.int32)
    cu = jnp.asarray([0, 5, 6], jnp.int32)
    kvl = jnp.asarray([30, 13], jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (6, 4, 128)), jnp.float32)
    kw = dict(scale=0.1, max_q_len=5, backend="pallas", interpret=True)
    for layer in range(3):
        got = ragged_paged_attention(q, pool, tbl, cu, kvl,
                                     layer=jnp.int32(layer), **kw)
        alone = ragged_paged_attention(q, pool[layer], tbl, cu, kvl, **kw)
        assert np.array_equal(np.asarray(got), np.asarray(alone))
        ref = ragged_paged_attention_ref(q, pool, tbl, cu, kvl, scale=0.1,
                                         layer=layer)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_launch_pages_are_the_pages_the_kernel_reads():
    """``ops.launch_pages``, the count the engine meters, against the
    pages the kernel reads: per sequence and query tile the non-null
    slots from the window's lower bound up to the tile's causal bound.
    With every other page of the pool turned to NaN the output is bit
    for bit the same, so the kernel reads no page outside that set; and
    the count is the set's size summed over tiles."""
    rng = np.random.default_rng(11)
    ps, D, window = 8, 128, 9
    pool = rng.normal(0, 1, (2, 24, ps, 4, D)).astype(np.float32)
    tbl = np.asarray([[3, 0, 7, 1, 9, 0], [5, 2, 0, 0, 0, 0],
                      [0, 0, 0, 0, 0, 0], [4, 6, 8, 10, 0, 0]], np.int32)
    qlens, kvl = [37, 1, 1, 5], np.asarray([41, 13, 0, 30], np.int32)
    cu = np.concatenate([[0], np.cumsum(qlens)]).astype(np.int32)
    q = jnp.asarray(rng.normal(0, 1, (int(cu[-1]), 4, D)), jnp.float32)
    for win in (None, window):
        bq = resolve_config(ps, D, max(qlens)).block_q
        read, count = set(), 0
        for s, (ql, kl) in enumerate(zip(qlens, kvl)):
            for q_lo in range(0, ql, bq) if kl else ():
                last = min(kl - ql + q_lo + bq, kl) - 1
                lo = 0 if win is None else max(kl - ql + q_lo - win + 1,
                                               0) // ps
                pages = [p for p in tbl[s, lo:last // ps + 1] if p]
                read |= set(pages)
                count += len(pages)
        assert ops.launch_pages(tbl, cu, kvl, page_size=ps, head_dim=D,
                                max_q_len=max(qlens), window=win) == count
        poisoned = pool.copy()
        poisoned[:, [p for p in range(24) if p not in read]] = np.nan
        kw = dict(scale=0.1, window=win, max_q_len=max(qlens),
                  layer=jnp.int32(1), backend="pallas", interpret=True)
        clean = ragged_paged_attention(q, jnp.asarray(pool), tbl, cu, kvl,
                                       **kw)
        dirty = ragged_paged_attention(q, jnp.asarray(poisoned), tbl, cu,
                                       kvl, **kw)
        live = np.arange(int(cu[-1])) < cu[2]  # the idle row is unwritten
        live |= np.arange(int(cu[-1])) >= cu[3]
        assert np.array_equal(np.asarray(clean)[live],
                              np.asarray(dirty)[live])


def test_launches_share_one_traced_kernel(monkeypatch):
    """Chunks of 20 and 28 rows (one power-of-2 bucket) over tables 4 and
    8 slots wide, each in a program of its own, trace the kernel body
    once: every step program of the engine would otherwise trace its
    own, which is most of a program's set-up. The results are those of
    the reference."""
    from repro.kernels.paged_attention import kernel

    body, traced = kernel._kernel, []

    def counted(*args, **kw):
        traced.append(1)
        return body(*args, **kw)

    monkeypatch.setattr(kernel, "_kernel", counted)
    _three_launches()
    assert len(traced) == 1


def _three_launches():
    rng = np.random.default_rng(13)
    pool = jnp.asarray(rng.normal(0, 1, (19, 8, 2, 128)), jnp.float32)
    for rows, width in ((20, 4), (28, 8), (28, 4)):
        tbl = np.zeros((1, width), np.int32)
        tbl[0, :4] = [7, 3, 11, 5]
        q = jnp.asarray(rng.normal(0, 1, (rows, 2, 128)), jnp.float32)
        cu = jnp.asarray([0, rows], jnp.int32)
        kvl = jnp.asarray([rows + 3], jnp.int32)
        got = jax.jit(lambda q, t, c, k: ragged_paged_attention(
            q, pool, t, c, k, scale=0.1, max_q_len=rows, backend="pallas",
            interpret=True))(q, jnp.asarray(tbl), cu, kvl)
        ref = ragged_paged_attention_ref(q, pool, jnp.asarray(tbl), cu, kvl,
                                         scale=0.1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name,platform,kernel", [
    ("qwen3-8b", "tpu", True),            # 2*Hkv = 16, head dim 128
    ("deepseek-7b", "tpu", True),         # 2*Hkv = 64
    ("qwen3-8b", "cpu", False),           # the interpreter would serve
    ("hymba-1.5b", "tpu", False),         # hybrid mixer, 2*Hkv = 10
    ("deepseek-v2-lite-16b", "tpu", False),   # latent pages, head dim 576
])
def test_backend_picks_the_kernel_by_device_and_geometry(name, platform,
                                                         kernel):
    assert ComputeBackend.kernel_serves(get_config(name),
                                        platform) is kernel
