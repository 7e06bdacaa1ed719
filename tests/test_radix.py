"""Radix prefix-tree invariants (hypothesis property tests + deterministic
manager-level refcount/pinning checks).

Invariants under arbitrary insert/match/evict sequences:

- **token conservation** — the tree holds exactly the distinct page-aligned
  prefixes inserted (one page per distinct (path, page) pair), and
  evictions remove whole leaves' tokens, never a partial page;
- **refcount consistency** — every page's refcount equals (1 if the tree
  holds it) + (number of live sessions holding it); closing sessions and
  draining the tree releases every region;
- **pinned nodes are never evicted** — a live session pins its matched /
  registered path; eviction only ever removes unlocked leaves.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.core.memclass import HBM3E, MRM_RRAM
from repro.core.simulator import MemorySystem
from repro.serving import PagedKVManager, RadixKVIndex
from repro.serving.kv_cache import Page

PT = 4  # page_tokens for the pure-tree tests


def _mk_pages(tokens):
    return [Page(page_id=i, region_id=None, n_tokens=PT, sealed=True)
            for i in range(len(tokens) // PT)]


def _distinct_page_prefixes(seqs):
    """Ground truth: the set of (page-aligned prefix) paths a radix tree
    over `seqs` must hold — one page per distinct prefix."""
    out = set()
    for s in seqs:
        for k in range(1, len(s) // PT + 1):
            out.add(tuple(s[:k * PT]))
    return out


from _hypothesis_compat import HAS_HYPOTHESIS

if HAS_HYPOTHESIS:
    seq_strategy = st.lists(
        st.lists(st.integers(min_value=0, max_value=3), min_size=PT,
                 max_size=6 * PT).map(lambda s: s[:len(s) // PT * PT]),
        min_size=1, max_size=12)
else:  # shim: @given skips these tests; the strategy is never drawn
    seq_strategy = None


@given(seq_strategy)
@settings(max_examples=60, deadline=None)
def test_radix_insert_conserves_tokens_and_match_is_exact(seqs):
    seqs = [s for s in seqs if s]
    tree = RadixKVIndex(PT)
    for t, s in enumerate(seqs):
        tree.insert(s, _mk_pages(s), now=float(t))
    want = _distinct_page_prefixes(seqs)
    # one page per distinct page-aligned prefix; tokens conserved
    assert tree.total_pages() == len(want)
    assert tree.total_tokens() == PT * len(want)
    # match_len returns the longest inserted page-aligned prefix, exactly
    for s in seqs:
        probe = list(s) + [7]  # diverging tail never extends the match
        got = tree.match_len(probe)
        truth = max((len(p) for p in want
                     if tuple(probe[:len(p)]) == p), default=0)
        assert got == truth


@given(seq_strategy)
@settings(max_examples=40, deadline=None)
def test_radix_evict_drains_tree_and_conserves_pages(seqs):
    seqs = [s for s in seqs if s]
    tree = RadixKVIndex(PT)
    held = []
    for t, s in enumerate(seqs):
        _, inserted, _ = tree.insert(s, _mk_pages(s), now=float(t))
        held += inserted
    evicted_pages = []
    while True:
        leaf = tree.pop_lru_leaf()
        if leaf is None:
            break
        evicted_pages += leaf.pages
        # a leaf eviction removes whole pages, never splits one
        assert leaf.n_tokens == PT * len(leaf.pages)
    assert tree.n_nodes() == 0 and tree.total_tokens() == 0
    # every page the tree held came back out exactly once
    assert sorted(map(id, evicted_pages)) == sorted(map(id, held))


@given(seq_strategy, st.integers(min_value=0, max_value=11))
@settings(max_examples=40, deadline=None)
def test_radix_locked_paths_survive_full_eviction(seqs, pin_idx):
    seqs = [s for s in seqs if s]
    tree = RadixKVIndex(PT)
    for t, s in enumerate(seqs):
        tree.insert(s, _mk_pages(s), now=float(t))
    pinned = seqs[pin_idx % len(seqs)]
    m = tree.match(pinned, now=100.0)
    tree.lock(m.node)
    pinned_tokens = m.tokens
    while tree.pop_lru_leaf() is not None:
        pass
    # the pinned path (and nothing below it) survives
    assert tree.total_tokens() == pinned_tokens
    assert tree.match_len(pinned) == pinned_tokens
    tree.unlock(m.node)
    while tree.pop_lru_leaf() is not None:
        pass
    assert tree.n_nodes() == 0


def test_radix_lru_order_and_parent_exposure():
    """Leaf-LRU: oldest unlocked leaf goes first; freeing a leaf exposes
    its parent as the next candidate."""
    tree = RadixKVIndex(PT)
    a = [1] * PT + [2] * PT
    b = [1] * PT + [3] * PT
    tree.insert(a, _mk_pages(a), now=1.0)
    tree.insert(b, _mk_pages(b), now=2.0)
    # tree: [1]*PT -> {[2]*PT, [3]*PT}; leaves are the two tails
    v1 = tree.pop_lru_leaf()
    assert v1.key == tuple([2] * PT)   # older leaf first
    v2 = tree.pop_lru_leaf()
    assert v2.key == tuple([3] * PT)
    v3 = tree.pop_lru_leaf()           # parent now a leaf
    assert v3.key == tuple([1] * PT)
    assert tree.pop_lru_leaf() is None


# ---------------------------------------------------------------------------
# Manager level: refcounts vs live sessions, pinning, region release
# ---------------------------------------------------------------------------


def _mgr(page_tokens=PT, gb=8):
    cfg = get_config("qwen3-8b")
    mem = MemorySystem({"mrm": (MRM_RRAM, gb << 30), "hbm": (HBM3E, 1 << 30)})
    return PagedKVManager(cfg, mem, "mrm", page_tokens=page_tokens), mem


def _check_refcounts(kv):
    """Every page's refcount == tree-holds-it + #sessions holding it."""
    in_tree = {id(p) for n in kv.radix.nodes() for p in n.pages}
    holds = {}
    for s in kv.sessions.values():
        for p in s.pages:
            holds[id(p)] = holds.get(id(p), 0) + 1
    pages = {id(p): p for s in kv.sessions.values() for p in s.pages}
    for n in kv.radix.nodes():
        for p in n.pages:
            pages[id(p)] = p
    for pid, p in pages.items():
        want = (1 if pid in in_tree else 0) + holds.get(pid, 0)
        assert p.refcount == want, (p, want)


@given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 40)),
                min_size=1, max_size=10))
@settings(max_examples=30, deadline=None)
def test_manager_refcounts_track_sessions_and_tree(ops):
    """Random open(shared-family prompt)/append/register/close traffic
    keeps page refcounts consistent with live sessions at every step, and
    full teardown releases every region."""
    kv, mem = _mgr()
    families = {f: list(range(10 * f, 10 * f + 3)) * 20 for f in range(4)}
    sid = 0
    live = []
    for fam, n_tokens in ops:
        prompt = families[fam][:max(n_tokens, 1)]
        m = kv.match_prefix(prompt)
        kv.open_session(sid, match=m)
        have = kv.sessions[sid].tokens
        if len(prompt) > have:
            kv.append_tokens(sid, len(prompt) - have)
        kv.register_prefix(sid, prompt)
        live.append(sid)
        sid += 1
        _check_refcounts(kv)
        if len(live) > 2:          # close the oldest session
            kv.close_session(live.pop(0))
            _check_refcounts(kv)
    for s in live:
        kv.close_session(s)
    _check_refcounts(kv)
    kv.evict_prefixes()
    assert kv.radix.n_nodes() == 0
    assert kv.live_pages() == 0
    # every region released: the tier's allocator is back to empty
    assert mem.devices["mrm"].alloc.utilization == 0.0


def test_manager_never_evicts_pinned_prefix():
    """A live session pins its matched path: leaf-LRU eviction (pressure
    or watermark) must never free pages under it."""
    kv, _ = _mgr()
    prompt = list(range(100, 100 + 8 * PT))
    kv.open_session(0, match=kv.match_prefix(prompt))
    kv.append_tokens(0, len(prompt))
    kv.register_prefix(0, prompt)
    # session 1 attaches the shared prefix and stays live
    m = kv.match_prefix(prompt)
    assert m.tokens > 0
    kv.open_session(1, match=m)
    kv.close_session(0)
    kv.evict_prefixes()          # drain everything evictable
    s1 = kv.sessions[1]
    assert all(p.refcount >= 1 for p in s1.pages)
    assert all(p.region_id is not None for p in s1.pages)
    kvb = kv.kv_bytes_token
    assert kv.read_pages(1, [p.n_tokens * kvb for p in s1.pages]) \
        == s1.tokens * kvb
    kv.close_session(1)
    kv.evict_prefixes()
    assert kv.live_pages() == 0 and kv.radix.n_nodes() == 0


def test_register_moves_pin_to_deepest_node():
    """After publishing its prefix, a session pins the new leaf — its own
    freshly shared pages cannot be evicted while it lives."""
    kv, _ = _mgr()
    prompt = list(range(4 * PT))
    kv.open_session(0, match=kv.match_prefix(prompt))
    kv.append_tokens(0, len(prompt))
    kv.register_prefix(0, prompt)
    assert kv.evict_prefixes() == 0          # leaf pinned by session 0
    assert kv.radix.n_nodes() > 0
    kv.close_session(0)
    assert kv.evict_prefixes() > 0
    assert kv.radix.n_nodes() == 0


def test_match_is_page_aligned_and_capped():
    kv, _ = _mgr()
    prompt = list(range(50))                  # 12 full pages + 2 spare
    kv.open_session(0, match=kv.match_prefix(prompt))
    kv.append_tokens(0, 50)
    kv.register_prefix(0, prompt)
    m = kv.match_prefix(prompt, max_tokens=49)
    assert m.tokens == 48 and m.tokens % PT == 0
    m2 = kv.match_prefix(prompt[:11])         # partial page tail ignored
    assert m2.tokens == 8
    kv.close_session(0)


def test_multicodebook_tokens_match():
    """2-D (token, codebook) prompts radix-match like flat ones."""
    tree = RadixKVIndex(2)
    seq = np.arange(12, dtype=np.int32).reshape(6, 2)
    tree.insert(seq, [Page(i, None, 2, sealed=True) for i in range(3)],
                now=0.0)
    assert tree.match_len(seq) == 6
    div = seq.copy()
    div[4] = [99, 99]
    assert tree.match_len(div) == 4
