"""Fleet-level prefix directory + cross-replica KV migration (DESIGN.md §7).

Covers the directory protocol (ownership registered on insert, dropped on
leaf eviction), the migration path (pages and refcounts conserved on both
replicas' ledgers, interconnect traffic metered as the pages' bytes), the
migrated-hit vs cold-start decode equivalence guarantee, and the
load-tiebreak fix (directory-owned hot-prefix bytes count as load).
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.memclass import HBM3E, MRM_RRAM
from repro.core.simulator import MemorySystem
from repro.serving import (ClusterFrontend, EngineConfig, PrefixDirectory,
                           ServeEngine)


# ---------------------------------------------------------------------------
# PrefixDirectory unit semantics
# ---------------------------------------------------------------------------


def test_directory_register_lookup_invalidate():
    d = PrefixDirectory(page_tokens=4)
    path = list(range(12))                    # 3 pages
    d.register(0, path)
    # every page-aligned prefix is owned; longest match wins
    assert d.lookup(path) == (12, {0})
    assert d.lookup(path[:7]) == (4, {0})     # page-aligned, not 7
    assert d.lookup([99] * 8) == (0, None)
    d.register(1, path[:8])                   # second replica, shorter path
    assert d.lookup(path)[1] == {0}
    assert d.lookup(path[:8])[1] == {0, 1}
    # leaf eviction on replica 0 drops only the run the leaf covered
    d.invalidate(0, path, tail_tokens=4)      # deepest page leaves 0's tree
    assert d.lookup(path) == (8, {0, 1})
    # ancestors remain owned by 0 until their own eviction
    d.invalidate(0, path[:8], tail_tokens=8)
    assert d.lookup(path) == (8, {1})
    d.invalidate(1, path[:8], tail_tokens=8)
    assert d.lookup(path) == (0, None)
    assert d.n_entries() == 0


def test_directory_multicodebook_keys_normalized():
    d = PrefixDirectory(page_tokens=2)
    seq = np.arange(8, dtype=np.int32).reshape(4, 2)
    d.register(0, seq)
    assert d.lookup(seq) == (4, {0})
    assert d.lookup([[0, 1], [2, 3]]) == (2, {0})


# ---------------------------------------------------------------------------
# Engine-integrated: ownership follows the tree
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster_setup():
    from repro.models import init_params
    full = get_config("deepseek-7b")
    cfg = reduced(full)
    return full, cfg, init_params(cfg, jax.random.key(0))


def _mk_engine(full, cfg, params, **kw):
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    ecfg = dict(max_slots=2, max_cache_len=96, weight_tier="hbm",
                kv_tier="mrm", eos_token=-1, chunk_tokens=16, page_tokens=16)
    ecfg.update(kw)
    return ServeEngine(cfg, params, mem, EngineConfig(**ecfg), account_cfg=full)


def test_ownership_registered_on_insert_dropped_on_eviction(cluster_setup):
    full, cfg, params = cluster_setup
    fe = ClusterFrontend([_mk_engine(full, cfg, params) for _ in range(2)])
    prompt = list(range(2, 66))               # 64 tokens = 4 pages
    r0 = fe.submit(list(prompt), 4, session_key="a")
    fe.run_until_idle()
    home = fe.replica_of(r0)
    key = fe.engines[home].radix_key_for(prompt)
    matched, owners = fe.directory.lookup(key)
    assert matched > 0 and owners == {home}
    assert fe.directory.owned_by(home) > 0
    # draining the tree invalidates every prefix the replica owned
    fe.engines[home].kv.evict_prefixes()
    assert fe.engines[home].kv.radix.n_nodes() == 0
    assert fe.directory.lookup(key) == (0, None)
    assert fe.directory.owned_by(home) == 0
    assert fe.directory.invalidations > 0


def test_migration_conserves_pages_and_refcounts(cluster_setup):
    """A forced migration (gap -1: any queued owner loses) grafts the
    prefix on the receiver with both replicas' ledgers intact: donor pages
    untouched, receiver pages tree-owned (refcount 1), every region
    released when sessions close and both trees drain."""
    full, cfg, params = cluster_setup
    engines = [_mk_engine(full, cfg, params) for _ in range(2)]
    fe = ClusterFrontend(engines, migrate_prefixes=True, migrate_load_gap=-1)
    prompt = list(range(2, 66))
    r0 = fe.submit(list(prompt), 4, session_key="a")
    fe.run_until_idle()
    home = fe.replica_of(r0)
    other = 1 - home
    donor_pages = {id(p) for n in engines[home].kv.radix.nodes()
                   for p in n.pages}
    r1 = fe.submit(list(prompt) + [400], 4, session_key="b")
    assert fe.replica_of(r1) == other          # migrated, request followed
    assert fe.migrations == 1
    assert fe.migration_bytes > 0 and fe.migration_s > 0
    assert engines[other].kv.radix_stats.adopted_pages > 0
    # donor's pages were copied, not moved: same objects, refcount intact
    assert {id(p) for n in engines[home].kv.radix.nodes()
            for p in n.pages} == donor_pages
    for n in engines[home].kv.radix.nodes():
        for p in n.pages:
            assert p.refcount >= 1
    # receiver's adopted pages are distinct objects, tree-owned
    adopted = [p for n in engines[other].kv.radix.nodes() for p in n.pages]
    assert donor_pages.isdisjoint({id(p) for p in adopted})
    fe.run_until_idle()
    # the migrated request arrived as a real cross-replica hit, and the
    # scheduler counted its grafted prefix as an admission match
    assert engines[other].kv.prefix_hits_migrated >= 1
    assert engines[other].sched.stats.migrated_admissions >= 1
    # full teardown: every region on both replicas goes back
    for e in engines:
        e.kv.evict_prefixes()
        assert e.kv.radix.n_nodes() == 0
        assert e.kv.live_pages() == 0
        assert e.mem.devices["mrm"].alloc.utilization == 0.0
    # directory forgot both replicas
    key = engines[0].radix_key_for(prompt)
    assert fe.directory.lookup(key) == (0, None)


def test_migration_moves_exactly_the_new_tokens_kv_bytes(cluster_setup):
    """A migration's wire bytes are the KV bytes of the tokens newly
    backed on the receiver, nothing more: attention pages carry no state,
    and the engine publishes no compute snapshots beside its pages. The
    donor reads the same bytes out of its tiers."""
    full, cfg, params = cluster_setup
    engines = [_mk_engine(full, cfg, params) for _ in range(2)]
    fe = ClusterFrontend(engines, migrate_prefixes=True, migrate_load_gap=-1)
    prompt = list(range(2, 66))               # 64 tokens = 4 pages
    r0 = fe.submit(list(prompt), 4, session_key="a")
    fe.run_until_idle()
    home = fe.replica_of(r0)
    other = 1 - home
    kvb = engines[other].kv.kv_bytes_token
    assert kvb > 0 and engines[other].kv.state_bytes_page == 0
    reads0 = engines[home].mem.devices["mrm"].stats.read_bytes
    r1 = fe.submit(list(prompt) + [400], 4, session_key="b")
    assert fe.replica_of(r1) == other
    assert fe.migrations == 1 and fe.migrated_tokens == 64
    assert fe.migration_bytes == 64 * kvb
    assert fe.migration_s == pytest.approx(
        fe.migration_bytes / (fe.interconnect_gbps * 1e9))
    donor_reads = engines[home].mem.devices["mrm"].stats.read_bytes - reads0
    assert donor_reads == pytest.approx(fe.migration_bytes)
    fe.run_until_idle()
    assert fe.report()["interconnect"]["migration_bytes"] == 64 * kvb


@pytest.fixture(scope="module")
def f32_setup():
    full = get_config("deepseek-7b")
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    from repro.models import init_params
    return full, cfg, init_params(cfg, jax.random.key(0))


def test_migrated_hit_decodes_identically_to_cold_start(f32_setup):
    """Acceptance: a migrated hit (receiver computing on the donor's
    transferred pages, prefill extended from the boundary) decodes the
    exact tokens a never-saw-the-prefix cold engine decodes."""
    full, cfg, params = f32_setup
    rng = np.random.default_rng(17)
    shared = list(rng.integers(2, 400, 48))
    borrower = shared + list(rng.integers(2, 400, 8))

    engines = [_mk_engine(full, cfg, params) for _ in range(2)]
    fe = ClusterFrontend(engines, migrate_prefixes=True, migrate_load_gap=-1)
    r0 = fe.submit(shared + list(rng.integers(2, 400, 8)), 6, session_key="a")
    fe.run_until_idle()
    home = fe.replica_of(r0)
    r1 = fe.submit(list(borrower), 6, session_key="b")
    fe.run_until_idle()
    assert fe.replica_of(r1) == 1 - home       # served off the migrated copy
    target = fe.engines[1 - home]
    assert target.kv.prefix_hits_migrated >= 1
    assert target.prefill_tokens_skipped > 0   # compute actually donated

    cold = _mk_engine(full, cfg, params)
    cold.submit(list(borrower), 6)
    cold.run_until_idle()
    assert fe.output(r1) == cold.outputs[0]


def test_load_tiebreak_counts_directory_owned_prefix_bytes(cluster_setup):
    """Bugfix: a replica stuffed with pinned shared prefixes (radix-tree
    resident, no live sessions) must lose least-loaded ties to a really
    empty replica."""
    full, cfg, params = cluster_setup
    engines = [_mk_engine(full, cfg, params) for _ in range(2)]
    fe = ClusterFrontend(engines)
    # replica 0 serves (and registers) a prompt; no sessions stay live
    fe.submit(list(range(2, 66)), 4)
    fe.run_until_idle()
    assert engines[0].kv.radix_kv_bytes() > 0
    assert engines[0].kv.live_kv_bytes() == 0
    # equal queue lengths, but 0 holds hot-prefix KV -> 1 wins the tie
    assert fe.route() == 1
    engines[0].kv.evict_prefixes()
    assert fe.route() == 0                     # bytes gone -> index order


def test_adopt_prefix_partial_under_pressure_keeps_ledger_balanced():
    """Adoption into a nearly-full tier truncates at a page boundary
    (optional transfer: no unresolved pressure events), and what was
    adopted is tree-owned and releasable."""
    from repro.serving import PagedKVManager
    cfg = get_config("qwen3-8b")
    mem = MemorySystem({"mrm": (MRM_RRAM, 1 << 22), "hbm": (HBM3E, 1 << 30)})
    kv = PagedKVManager(cfg, mem, "mrm", page_tokens=4,
                        policy="evict-lru")
    tokens = list(range(400))                 # ~100 pages, way over capacity
    new_tok, total, node = kv.adopt_prefix(tokens)
    assert 0 < new_tok < 400 and new_tok % 4 == 0
    assert total == new_tok and node is not None
    assert kv.pressure.unresolved == 0 and kv.pressure.events == 0
    assert all(p.refcount == 1 for n in kv.radix.nodes() for p in n.pages)
    # a second adoption of the same path is a no-op (already held)
    new2, total2, _ = kv.adopt_prefix(tokens[:new_tok])
    assert new2 == 0 and total2 == new_tok
    kv.evict_prefixes()
    assert kv.radix.n_nodes() == 0
    assert mem.devices["mrm"].alloc.utilization == 0.0


def test_migration_admission_control_queues_on_busy_link(cluster_setup):
    """ROADMAP satellite: each receiver has ONE modelled interconnect
    link. Two migrations pulled into the same replica within one submit
    burst serialize on it — the second finds the link busy and reports a
    nonzero queue wait, and the receiver's clock ends at the delivery
    time of the last transfer (both TTFTs pay)."""
    full, cfg, params = cluster_setup
    engines = [_mk_engine(full, cfg, params) for _ in range(2)]
    fe = ClusterFrontend(engines, migrate_prefixes=True, migrate_load_gap=-1)
    p1 = list(range(2, 66))                    # two distinct 4-page prefixes
    p2 = list(range(200, 264))
    r0 = fe.submit(list(p1), 4, session_key="seed")
    fe.submit(list(p2), 4, session_key="seed")  # sticky: same home replica
    fe.run_until_idle()
    home = fe.replica_of(r0)
    other = 1 - home
    # pile queued work on the owner so both borrowers out-migrate to the
    # idle replica within ONE burst (no cluster step between submits)
    for i in range(3):
        engines[home].submit(list(range(400 + i, 440 + i)), 2)
    t0 = engines[other].mem.now
    b1 = fe.submit(p1 + [300], 4, session_key="b1")
    b2 = fe.submit(p2 + [301], 4, session_key="b2")
    assert fe.replica_of(b1) == other and fe.replica_of(b2) == other
    assert fe.migrations == 2
    # the second transfer queued behind the first on the receiver's link
    assert fe.migrations_queued >= 1
    assert fe.migration_queue_wait_s > 0
    rep_done = fe.run_until_idle()
    inter = rep_done["interconnect"]
    assert inter["queued_migrations"] == fe.migrations_queued
    assert inter["queue_wait_s"] == pytest.approx(fe.migration_queue_wait_s)
    # the receiver stalled to the serialized delivery time: transfer
    # durations + the queue wait all passed through its clock
    assert engines[other].mem.now - t0 >= (
        inter["migration_s"] + inter["queue_wait_s"]) - 1e-9
    # and the work still decodes: every request finished
    assert rep_done["finished"] == 7


def test_directory_shard_counters_prove_load_balance():
    """ISSUE 10 satellite: the hash-sharded directory spreads digest keys
    across every shard (Fibonacci mixing on the page digests), keeps the
    per-shard lookup/update counters, and batches invalidations as deltas
    (``delta_batches <= delta_ops``: O(changes) mutations, not a
    per-prefix broadcast)."""
    d = PrefixDirectory(page_tokens=4, n_shards=8)
    rng = np.random.default_rng(0)
    paths = [list(rng.integers(0, 1000, 16)) for _ in range(200)]
    for i, path in enumerate(paths):
        d.register(i % 4, path)
        d.lookup(path)
    c = d.shards.shard_counters()
    assert c["n_shards"] == 8
    assert sum(c["entries"]) == d.n_entries()
    assert min(c["entries"]) > 0, f"idle shard: {c['entries']}"
    mean = sum(c["entries"]) / c["n_shards"]
    assert max(c["entries"]) < 3 * mean, f"shard hot spot: {c['entries']}"
    assert min(c["lookups"]) > 0 and sum(c["updates"]) > 0
    assert 0 < c["delta_batches"] <= c["delta_ops"]
    # delta invalidation drains exactly what was registered
    for i, path in enumerate(paths):
        d.invalidate(i % 4, path, tail_tokens=len(path))
    assert d.n_entries() == 0
    assert all(n == 0 for n in d.shards.shard_counters()["entries"])


def _replication_sequence(fe, prompt):
    """Seed one owner, then three extension hits: the second crosses a
    replicate_threshold of 2 and pushes toward the non-owners. In
    lockstep mode the first push makes the donor's up-link hot, so the
    same-instant second copy *defers* (admission control, no retry loop
    outside the event plane) — the third hit re-crosses the threshold on
    a cold fabric and fills the remaining copy."""
    fe.submit(list(prompt), 4, session_key="seed")
    fe.run_until_idle()
    for i, key in enumerate(("h1", "h2", "h3")):
        fe.submit(prompt + [301 + i], 4, session_key=key)
        rep = fe.run_until_idle()
    return rep


def test_predictive_replication_accounting_zero_imbalance(cluster_setup):
    """ISSUE 10 satellite: a speculative push meters its bytes exactly
    once on the fabric and exactly once into the receiver's tier — the
    fabric byte ledger equals demand migration bytes + replication bytes
    with zero imbalance, and the replicas' page ledgers still drain."""
    full, cfg, params = cluster_setup
    engines = [_mk_engine(full, cfg, params) for _ in range(3)]
    fe = ClusterFrontend(engines, migrate_prefixes=True,
                         migrate_load_gap=100,     # no demand migrations
                         replicate_threshold=2, replicate_copies=2)
    prompt = list(range(2, 66))
    rep = _replication_sequence(fe, prompt)
    home = fe.replica_of(min(fe.requests))         # the seed's owner
    inter = rep["interconnect"]
    assert fe.replications == 2                    # both non-owners warmed
    assert inter["replications"] == 2
    assert inter["replication_bytes"] > 0
    assert inter["replicated_tokens"] == 2 * 64
    assert inter["migrations"] == 0                # speculative != demand
    # the invariant: every fabric byte is one demand or speculative byte
    imbalance = fe.fabric.bytes_total - (fe.migration_bytes
                                         + fe.replication_bytes)
    assert imbalance == pytest.approx(0.0)
    assert rep["fabric"]["bytes"] == fe.fabric.bytes_total
    # the receivers really adopted the pages (tier write metered once)
    key = engines[0].radix_key_for(prompt)
    matched, owners = fe.directory.lookup(key)
    assert matched == 64 and owners == {0, 1, 2}
    for i, e in enumerate(engines):
        if i != home:
            assert e.kv.radix_stats.adopted_pages > 0, f"replica {i}"
    # teardown releases every adopted copy on every replica
    for e in engines:
        e.kv.evict_prefixes()
        assert e.kv.live_pages() == 0
        assert e.mem.devices["mrm"].alloc.utilization == 0.0
    assert fe.directory.lookup(key) == (0, None)


def test_event_mode_replication_matches_lockstep(cluster_setup):
    """The REPLICATION_PUSH event path delivers the same copies the
    lockstep path does — same replication count, same decoded tokens,
    same balanced fabric ledger — with pushes recorded in the trace."""
    full, cfg, params = cluster_setup

    def run_one(clock_mode):
        engines = [_mk_engine(full, cfg, params) for _ in range(3)]
        fe = ClusterFrontend(engines, migrate_prefixes=True,
                             migrate_load_gap=100, clock_mode=clock_mode,
                             record_trace=True,
                             replicate_threshold=2, replicate_copies=2)
        rep = _replication_sequence(fe, list(range(2, 66)))
        outs = {k: list(fe.output(r)) for k, r in
                zip(("seed", "h1", "h2", "h3"), sorted(fe.requests))}
        return fe, rep, outs

    fe_l, rep_l, outs_l = run_one("lockstep")
    fe_e, rep_e, outs_e = run_one("event")
    assert outs_l == outs_e, "replication changed decoded tokens"
    assert fe_e.replications == fe_l.replications == 2
    assert rep_e["interconnect"]["replication_bytes"] == pytest.approx(
        rep_l["interconnect"]["replication_bytes"])
    for fe in (fe_l, fe_e):
        assert fe.fabric.bytes_total == pytest.approx(
            fe.migration_bytes + fe.replication_bytes)
    assert fe_e.trace.n_events > 0


def test_fleet_report_interconnect_and_directory_sections(cluster_setup):
    full, cfg, params = cluster_setup
    fe = ClusterFrontend([_mk_engine(full, cfg, params) for _ in range(2)],
                         migrate_prefixes=True, migrate_load_gap=-1)
    fe.submit(list(range(2, 66)), 4, session_key="a")
    fe.run_until_idle()
    r1 = fe.submit(list(range(2, 66)) + [401], 4, session_key="b")
    rep = fe.run_until_idle()
    inter = rep["interconnect"]
    assert inter["migrations"] == 1
    assert inter["migration_bytes"] > 0
    assert inter["migration_s"] == pytest.approx(
        inter["migration_bytes"] / (inter["gbps"] * 1e9))
    # the request that triggered (and waited for) the transfer pays it:
    # its TTFT includes the interconnect time
    replica, local = fe.requests[r1]
    rec = next(r for r in fe.engines[replica].sched.latency
               if r["request_id"] == local)
    assert rec["ttft"] >= inter["migration_s"]
    assert rep["directory"]["entries"] > 0
    assert rep["directory"]["registrations"] > 0
    assert rep["prefix_hits_migrated"] >= 1
