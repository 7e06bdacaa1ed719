"""Serving-simulator benchmarks over the MRM control plane.

1. Retention-policy sweep (paper §4: DCM 'right-provisioning'): vary the
   DCM expected-session-lifetime programming and measure refresh overhead
   vs write energy — the knob the cluster control plane owns.
2. Cluster sweep: replica count x capacity-constrained MRM KV tier with
   chunked prefill — every failed KV allocation must be resolved by an
   explicit eviction/spill/recompute decision (zero silent drops), and the
   fleet report aggregates tokens/bytes across replicas.
3. Prefix-reuse sweep: shared-prefix traffic (multi-turn chat, shared
   system prompts, RAG fan-out) with the radix prefix tree on vs off —
   reuse must cut prefill tokens computed by >= 30% at equal (identical)
   output tokens, and the hit rate / tokens reused / TTFT land in the
   JSON trajectory. Runs once per resumption kind (DESIGN.md §8):
   attention (deepseek-7b), SSM state pages (mamba2-2.7b) and the hybrid
   union (hymba-1.5b); stacks with a KV write stream must also cut
   KV-tier write bytes >= 30%.
4. Tail-reuse sweep (DESIGN.md §9): shared prefixes whose length
   straddles a page boundary, tail-copy on vs the page-aligned matcher —
   the prefill-token cut with sub-page tails must strictly exceed the
   page-aligned cut at identical decoded tokens, with tail-copy bytes
   metered and pressure ledgers balanced.
5. Fleet-reuse sweep: N replicas x shared-prefix fan-out with the fleet
   prefix directory + cross-replica migration on vs the per-replica radix
   baseline (each replica recomputes the shared head cold) — must show a
   cross-replica hit rate > 0, a >= 20% fleet prefill-token cut at
   identical decoded tokens, non-zero metered interconnect traffic, and
   zero pressure-ledger imbalance. Runs for an attention stack and an SSM
   stack (the latter ships recurrent-state pages over the wire).
6. Reliability sweep (DESIGN.md §11; suite ``reliability``, trajectory in
   ``BENCH_reliability.json``): fault injection over the paged plane at a
   target RBER, three arms on identical prompts — clean (no injection),
   protected (domain ECC + refresh: scrubs fire, decode matches clean
   within tolerance) and over-aged (refresh disabled, the clock jumped
   past 4x retention: uncorrectable blocks reported, decode measurably
   degrades) — plus the per-state ECC overhead ladder showing the split
   code shrinking check bits on demoted/cold/spilled pages.
7. Replication sweep (DESIGN.md §13; suite ``replication``, trajectory
   in ``BENCH_fleet.json``): reactive vs predictive prefix replication
   on the event-driven fleet simulator — the herald-led rag_storm
   fan-out must cut TTFT p95 >= 40% at bit-identical decoded tokens
   with speculative push bytes > 0 and strictly fewer demand
   migrations; the diurnal tenant mix must eliminate demand migrations
   at flat TTFT; trace digests bit-stable across reruns and submission
   shuffles.
"""
from __future__ import annotations

import time

import jax
import numpy as np


def prefix_workloads(rng, vocab: int, n_users: int = 3, turns: int = 2,
                     fanout: int = 4, n_system: int = 5) -> list:
    """Shared-prefix traffic at three granularities. Returns
    ``[(prompt_tokens, max_new, session_key), ...]``:

    - **shared system prompt** — one 48-token head, distinct 16-token asks;
    - **multi-turn chat** — each user's context grows turn over turn (the
      next prompt extends the previous one, radix-matchable because the
      serving path keeps prompts unpadded / position-aligned);
    - **RAG fan-out** — one 64-token document, `fanout` question variants.
    """
    reqs = []
    system = list(rng.integers(2, vocab, 48))
    for i in range(n_system):
        reqs.append((system + list(rng.integers(2, vocab, 16)), 6, f"sys-{i}"))
    for u in range(n_users):
        hist = list(rng.integers(2, vocab, 24))
        for _ in range(turns):
            reqs.append((list(hist), 6, f"chat-{u}"))
            hist = hist + list(rng.integers(2, vocab, 12))  # model reply etc.
    doc = list(rng.integers(2, vocab, 64))
    for q in range(fanout):
        reqs.append((doc + list(rng.integers(2, vocab, 12)), 6, f"rag-{q}"))
    return reqs


def prefix_reuse(arch="deepseek-7b", **workload_kw) -> dict:
    """Radix prefix reuse on shared-prefix traffic vs prefix_caching=False:
    identical decoded tokens and >= 30% fewer prefill tokens computed —
    for *every* resumption kind (attention KV pages, MLA latent pages,
    SSM/hybrid state pages; DESIGN.md §8). Stacks with a real KV
    write stream must also cut KV-tier write bytes >= 30%; a pure-SSM
    stack appends no per-token KV, so that axis is reported as None.

    ``workload_kw`` scales :func:`prefix_workloads` — point stacks
    resume only at page boundaries (§8), so their savings grow with
    fan-out depth where positional stacks save every shared token."""
    from repro.configs import get_config, reduced
    from repro.core.memclass import HBM3E, MRM_RRAM
    from repro.core.simulator import MemorySystem
    from repro.models import init_params
    from repro.serving import EngineConfig, ServeEngine

    full = get_config(arch)
    # fp32 keeps extend-from-the-match-boundary greedy argmax bit-equal to
    # the cold prefill (bf16 amplifies accumulation-order differences)
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    reqs = prefix_workloads(np.random.default_rng(0), cfg.vocab_size,
                            **workload_kw)

    def run_one(prefix_caching: bool):
        mem = MemorySystem({"mrm": (MRM_RRAM, 1 << 40),
                            "hbm": (HBM3E, 1 << 37)})
        eng = ServeEngine(cfg, params, mem,
                          EngineConfig(max_slots=2, max_cache_len=96,
                                       weight_tier="hbm", kv_tier="mrm",
                                       eos_token=-1, chunk_tokens=16,
                                       page_tokens=16,
                                       prefix_caching=prefix_caching,
                                       radix_hot_threshold=2),
                          account_cfg=full)
        for prompt, max_new, _key in reqs:
            eng.submit(list(prompt), max_new)
        rep = eng.run_until_idle()
        return eng, rep

    eng_on, on = run_one(True)
    eng_off, off = run_one(False)
    assert on["tokens_generated"] == off["tokens_generated"]
    outs_on = {k: list(v) for k, v in eng_on.outputs.items()}
    outs_off = {k: list(v) for k, v in eng_off.outputs.items()}
    assert outs_on == outs_off, "prefix reuse changed decoded tokens"
    prefill_cut = 1 - on["prefill_tokens_computed"] / off["prefill_tokens_computed"]
    assert prefill_cut >= 0.30, f"prefill cut {prefill_cut:.2%} < 30%"
    kv_w_on = on["memory"]["tiers"]["mrm"]["write_gb"]
    kv_w_off = off["memory"]["tiers"]["mrm"]["write_gb"]
    kv_write_cut = None
    if full.kv_bytes_per_token() > 0:
        kv_write_cut = 1 - kv_w_on / kv_w_off
        assert kv_write_cut >= 0.30, f"KV write cut {kv_write_cut:.2%} < 30%"
    return {
        "requests": len(reqs),
        "snapshot_kind": on["prefix"]["snapshot_kind"],
        "prefix_hits": on["prefix_hits"],
        "prefix_hit_rate": on["prefix_hits"] / len(reqs),
        "tokens_reused": on["prefix_tokens_reused"],
        "tokens_skipped_compute": on["prefill_tokens_skipped"],
        "prefill_tokens_computed": on["prefill_tokens_computed"],
        "prefill_tokens_cold": off["prefill_tokens_computed"],
        "prefill_cut": prefill_cut,
        "kv_write_gb": kv_w_on,
        "kv_write_gb_cold": kv_w_off,
        "kv_write_cut": kv_write_cut,
        "retention_promotions": on["prefix"]["retention_promotions"],
        "ttft_p50_s": on["latency"]["ttft_p50"],
        "ttft_p95_s": on["latency"]["ttft_p95"],
        "ttft_p50_cold_s": off["latency"]["ttft_p50"],
        "itl_p50_s": on["latency"]["itl_p50"],
    }


def tail_reuse(arch="deepseek-7b", page_tokens=16, head_tokens=56,
               fanout=6, tail_len=9) -> dict:
    """Sub-page tail reuse (DESIGN.md §9) on prefix lengths that straddle
    page boundaries: the shared head is deliberately NOT page-aligned
    (``head_tokens % page_tokens != 0``), so a page-aligned matcher (the
    PR 4 behavior, ``tail_copy=False``) recomputes the mid-page tail on
    every hit while the tail-copy path resumes extend from the exact
    token boundary. Asserts, at identical decoded tokens across all three
    runs (tail on / page-aligned / prefix caching off):

    - the tail-on prefill-token cut **strictly exceeds** the page-aligned
      cut (the PR 4 baseline);
    - tail-copy bytes were actually metered (read + write over the bus);
    - every pressure ledger balances with zero unresolved events.
    """
    from repro.configs import get_config, reduced
    from repro.core.memclass import HBM3E, MRM_RRAM
    from repro.core.simulator import MemorySystem
    from repro.models import init_params
    from repro.serving import EngineConfig, ServeEngine

    assert head_tokens % page_tokens != 0, "head must straddle a page"
    full = get_config(arch)
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    head = list(rng.integers(2, cfg.vocab_size, head_tokens))
    prompts = [head + list(rng.integers(2, cfg.vocab_size, tail_len))
               for _ in range(fanout)]

    def run_one(tail_copy: bool, prefix_caching: bool = True):
        mem = MemorySystem({"mrm": (MRM_RRAM, 1 << 40),
                            "hbm": (HBM3E, 1 << 37)})
        eng = ServeEngine(cfg, params, mem,
                          EngineConfig(max_slots=2, max_cache_len=96,
                                       weight_tier="hbm", kv_tier="mrm",
                                       eos_token=-1, chunk_tokens=16,
                                       page_tokens=page_tokens,
                                       prefix_caching=prefix_caching,
                                       tail_copy=tail_copy),
                          account_cfg=full)
        for p in prompts:   # sequential: every later prompt can hit
            eng.submit(list(p), 6)
            eng.run_until_idle()
        return eng, eng.report()

    eng_tail, on = run_one(True)
    eng_page, page = run_one(False)
    eng_cold, cold = run_one(True, prefix_caching=False)
    outs = [{k: list(v) for k, v in e.outputs.items()}
            for e in (eng_tail, eng_page, eng_cold)]
    assert outs[0] == outs[1] == outs[2], "tail reuse changed decoded tokens"
    assert on["tokens_generated"] == page["tokens_generated"] \
        == cold["tokens_generated"]
    cut_tail = 1 - on["prefill_tokens_computed"] / cold["prefill_tokens_computed"]
    cut_page = 1 - page["prefill_tokens_computed"] / cold["prefill_tokens_computed"]
    assert cut_tail > cut_page, \
        f"tail cut {cut_tail:.2%} must strictly beat page-aligned {cut_page:.2%}"
    prefix = on["prefix"]
    assert prefix["tail_hits"] > 0, prefix
    assert prefix["tail_copy_bytes"] > 0, prefix
    for rep in (on, page, cold):
        p = rep["pressure"]
        assert p["events"] == (p["resolved_evict"] + p["resolved_spill"]
                               + p["resolved_recompute"] + p["unresolved"])
        assert p["unresolved"] == 0 and rep["dropped_allocs"] == 0
    return {
        "requests": len(prompts),
        "page_tokens": page_tokens,
        "head_tokens": head_tokens,
        "prefill_tokens_tail": on["prefill_tokens_computed"],
        "prefill_tokens_page_aligned": page["prefill_tokens_computed"],
        "prefill_tokens_cold": cold["prefill_tokens_computed"],
        "prefill_cut": cut_tail,
        "prefill_cut_page_aligned": cut_page,
        "tail_hits": prefix["tail_hits"],
        "tail_tokens_copied": prefix["tail_tokens_copied"],
        "tail_copy_bytes": prefix["tail_copy_bytes"],
        "tokens_skipped_compute": on["prefill_tokens_skipped"],
        "ttft_p50_s": on["latency"]["ttft_p50"],
        "ttft_p50_page_aligned_s": page["latency"]["ttft_p50"],
    }


def compute(arch="deepseek-7b") -> dict:
    from repro.configs import get_config, reduced
    from repro.core.memclass import HBM3E, MRM_RRAM
    from repro.core.simulator import MemorySystem
    from repro.models import init_params
    from repro.serving import EngineConfig, ServeEngine

    full = get_config(arch)
    cfg = reduced(full)
    params = init_params(cfg, jax.random.key(0))
    out = {}
    for session_s in (0.01, 1.0, 60.0, 3600.0):
        mem = MemorySystem({"mrm": (MRM_RRAM, 1 << 40), "hbm": (HBM3E, 1 << 37)})
        eng = ServeEngine(cfg, params, mem,
                          EngineConfig(max_slots=2, max_cache_len=64,
                                       weight_tier="mrm", kv_tier="mrm",
                                       expected_session_s=session_s),
                          account_cfg=full)
        rng = np.random.default_rng(0)
        for _ in range(4):
            eng.submit(list(rng.integers(2, cfg.vocab_size, 16)), 10)
        rep = eng.run_until_idle()
        mrm = rep["memory"]["tiers"]["mrm"]
        out[f"session_{session_s}s"] = {
            "refresh_events": rep["memory"]["refresh_stats"]["refresh"],
            "refresh_gb": mrm["refresh_gb"],
            "write_gb": mrm["write_gb"],
            "energy_per_token_j": rep["energy_per_token_j"],
            "refresh_overhead": mrm["refresh_gb"] / max(mrm["write_gb"], 1e-12),
        }
    return out


def cluster_sweep(arch="deepseek-7b", replica_counts=(1, 2),
                  kv_capacity_bytes=1 << 25, requests=8) -> dict:
    """Replica sweep under a capacity-constrained MRM KV tier: chunked
    prefill on, pressure policy 'evict-lru' (prefix-LRU eviction with
    drop-and-recompute fallback). Asserts the pressure ledger balances and
    no allocation was silently dropped."""
    from repro.configs import get_config, reduced
    from repro.core.memclass import HBM3E, MRM_RRAM
    from repro.core.simulator import MemorySystem
    from repro.models import init_params
    from repro.serving import ClusterFrontend, EngineConfig, ServeEngine

    full = get_config(arch)
    cfg = reduced(full)
    params = init_params(cfg, jax.random.key(0))
    out = {}
    for n in replica_counts:
        engines = []
        for _ in range(n):
            mem = MemorySystem({"mrm": (MRM_RRAM, kv_capacity_bytes),
                                "hbm": (HBM3E, 1 << 34)})
            engines.append(ServeEngine(
                cfg, params, mem,
                EngineConfig(max_slots=2, max_cache_len=64, weight_tier="hbm",
                             kv_tier="mrm", eos_token=-1, chunk_tokens=16,
                             page_tokens=16,
                             kv_pressure_policy="evict-lru",
                             kv_high_watermark=0.9),
                account_cfg=full))
        fe = ClusterFrontend(engines)
        rng = np.random.default_rng(0)
        for i in range(requests):
            fe.submit(list(rng.integers(2, cfg.vocab_size, 40)), 8,
                      session_key=f"user-{i}")
        rep = fe.run_until_idle()
        p = rep["pressure"]
        resolved = (p["resolved_evict"] + p["resolved_spill"] +
                    p["resolved_recompute"])
        assert p["events"] > 0, "tier was supposed to be capacity-constrained"
        assert p["events"] == resolved + p["unresolved"], p
        assert p["unresolved"] == 0, p
        assert rep["dropped_allocs"] == 0, \
            f"silent drops under pressure: {rep['dropped_allocs']}"
        assert rep["tokens_generated"] == sum(
            r["tokens_generated"] for r in rep["per_replica"])
        out[f"replicas_{n}"] = {
            "finished": rep["finished"],
            "tokens_generated": rep["tokens_generated"],
            "fleet_tokens_per_s": rep["fleet_tokens_per_s"],
            "energy_per_token_j": rep["energy_per_token_j"],
            "pressure_events": p["events"],
            "pressure_resolved": resolved,
            "prefix_evictions": p["prefix_evictions"],
            "recompute_tokens": p["recompute_tokens"],
            "dropped_allocs": rep["dropped_allocs"],
            "prefix_hits": rep["prefix_hits"],
            "prefix_tokens_reused": rep["prefix_tokens_reused"],
            "radix_routed": rep["radix_routed"],
            "ttft_p50_s": rep["latency"]["ttft_p50"],
        }
    return out


def fleet_reuse(arch="deepseek-7b", replicas=3, fanout=12,
                seed_tail_tokens=16) -> dict:
    """Fleet-level prefix reuse: a shared system-prompt head fanned out
    across a cluster. With the fleet directory + migration on, the head
    is computed cold exactly once and then *moved* (metered interconnect
    transfer) wherever load sends its traffic; the per-replica baseline
    (no fleet awareness: sticky/least-loaded routing, per-replica radix
    trees) recomputes it cold on every replica it lands on.

    ``seed_tail_tokens=0`` makes the seed prompt exactly the shared head,
    the shape that ships SSM/hybrid state pages for the boundary the
    fan-out matches (DESIGN.md §8)."""
    from repro.configs import get_config, reduced
    from repro.core.memclass import HBM3E, MRM_RRAM
    from repro.core.simulator import MemorySystem
    from repro.models import init_params
    from repro.serving import ClusterFrontend, EngineConfig, ServeEngine

    full = get_config(arch)
    # fp32: the migrated-hit extend path must stay bit-equal to cold
    # prefill (same policy as prefix_reuse above)
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    head = list(rng.integers(2, cfg.vocab_size, 64))
    seed_tail = list(rng.integers(2, cfg.vocab_size, seed_tail_tokens))
    tails = [list(rng.integers(2, cfg.vocab_size, 16)) for _ in range(fanout)]

    def run_one(fleet: bool):
        engines = []
        for _ in range(replicas):
            mem = MemorySystem({"mrm": (MRM_RRAM, 1 << 40),
                                "hbm": (HBM3E, 1 << 37)})
            engines.append(ServeEngine(
                cfg, params, mem,
                EngineConfig(max_slots=2, max_cache_len=96,
                             weight_tier="hbm", kv_tier="mrm",
                             eos_token=-1, chunk_tokens=16, page_tokens=16,
                             radix_hot_threshold=2),
                account_cfg=full))
        fe = ClusterFrontend(engines, migrate_prefixes=fleet,
                             interconnect_gbps=50.0, migrate_load_gap=1,
                             prefix_affinity=fleet)
        # wave 1 establishes the hot head on one replica...
        fe.submit(head + seed_tail, 6, session_key="seed")
        fe.run_until_idle()
        # ...then the fan-out wave arrives as a burst of distinct users
        for i, tail in enumerate(tails):
            fe.submit(head + tail, 6, session_key=f"fan-{i}")
        rep = fe.run_until_idle()
        outs = {r: list(fe.output(r)) for r in range(fanout + 1)}
        return fe, rep, outs

    fe_on, on, outs_on = run_one(True)
    fe_off, off, outs_off = run_one(False)
    assert on["tokens_generated"] == off["tokens_generated"]
    assert outs_on == outs_off, "fleet migration changed decoded tokens"

    def imbalance(rep):
        return sum(abs(r["pressure"]["events"]
                       - (r["pressure"]["resolved_evict"]
                          + r["pressure"]["resolved_spill"]
                          + r["pressure"]["resolved_recompute"]
                          + r["pressure"]["unresolved"]))
                   for r in rep["per_replica"])

    ledger_imbalance = imbalance(on) + imbalance(off)
    prefill_cut = 1 - (on["prefill_tokens_computed"]
                       / off["prefill_tokens_computed"])
    inter = on["interconnect"]
    assert ledger_imbalance == 0, (on["pressure"], off["pressure"])
    assert on["dropped_allocs"] == off["dropped_allocs"] == 0
    assert inter["migrations"] > 0 and inter["migration_bytes"] > 0, inter
    assert on["prefix_hits_migrated"] > 0, "no cross-replica hits"
    assert prefill_cut >= 0.20, f"fleet prefill cut {prefill_cut:.2%} < 20%"
    n_reqs = fanout + 1
    return {
        "replicas": replicas,
        "requests": n_reqs,
        "snapshot_kind": on["per_replica"][0]["prefix"]["snapshot_kind"],
        "prefill_tokens_fleet": on["prefill_tokens_computed"],
        "prefill_tokens_baseline": off["prefill_tokens_computed"],
        "prefill_cut": prefill_cut,
        "cross_replica_hits": on["prefix_hits_migrated"],
        "cross_replica_hit_rate": on["prefix_hits_migrated"] / n_reqs,
        "prefix_hits": on["prefix_hits"],
        "migrations": inter["migrations"],
        "migrated_tokens": inter["migrated_tokens"],
        "migration_bytes": inter["migration_bytes"],
        "migration_s": inter["migration_s"],
        "directory_entries": on["directory"]["entries"],
        "ledger_imbalance": ledger_imbalance,
        "dropped_allocs": on["dropped_allocs"],
        "ttft_p50_s": on["latency"]["ttft_p50"],
        "ttft_p50_baseline_s": off["latency"]["ttft_p50"],
    }


def reliability(arch="deepseek-7b", rber=1e-3, n_shares=3, head_tokens=32,
                ask_tokens=8, max_new=6, session_s=600.0) -> dict:
    """Fault-injection A/B gate (DESIGN.md §11). Three engine runs on the
    paged plane with identical prompts, greedy fp32 decode:

    - **clean** — domain ECC profile, refresh on, no injection;
    - **protected** — same, plus ``inject_rber``; after the first decode
      tokens the clock jumps to 80% of the refresh deadline, so the next
      page visits cross the scrub threshold deterministically (scrub-on-
      read corrects + re-arms, metered through the lifecycle) and decode
      must match the clean run within ``tolerance``;
    - **over-aged** — refresh servicing disabled and the clock jumped past
      4x the pages' programmed retention: RBER saturates, the strict code
      fails at the accounting scale (uncorrectable blocks > 0 in the
      report) and decoded tokens must degrade measurably vs protected.

    Also emits the per-retention-state ECC overhead ladder (mrm_rram)
    asserting the domain split code's check bits shrink vs the uniform
    code on every demoted-or-colder state — the density lever.
    """
    from repro.configs import get_config, reduced
    from repro.core.ecc import STATE_RETENTION_FRAC, TierEcc
    from repro.core.memclass import HBM3E, MRM_RRAM
    from repro.core.simulator import MemorySystem
    from repro.models import init_params
    from repro.serving import EngineConfig, ServeEngine

    full = get_config(arch)
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    head = list(rng.integers(2, cfg.vocab_size, head_tokens))
    prompts = [head + list(rng.integers(2, cfg.vocab_size, ask_tokens))
               for _ in range(n_shares)]
    # session pages are DCM-programmed at retention = 2 * session_s
    # (margin); the refresh deadline sits at half that
    retention_s = 2.0 * session_s
    deadline_s = retention_s / 2.0

    def run_one(inject, refresh=True, age_jump=0.0):
        mem = MemorySystem({"mrm": (MRM_RRAM, 1 << 40),
                            "hbm": (HBM3E, 1 << 37)},
                           ecc_profile="domain", service_refresh=refresh)
        eng = ServeEngine(cfg, params, mem,
                          EngineConfig(max_slots=2, max_cache_len=96,
                                       weight_tier="hbm", kv_tier="mrm",
                                       eos_token=-1, chunk_tokens=16,
                                       page_tokens=16, tail_copy=False,
                                       expected_session_s=session_s,
                                       inject_rber=inject, inject_seed=0),
                          account_cfg=full)
        for p in prompts:
            eng.submit(list(p), max_new)
        # run prefill through the first decode tokens, then age every page
        # with one clock jump before the remaining decode rounds
        steps = 0
        while not eng.sched.idle and eng.tokens_generated < 2 and steps < 500:
            eng.step()
            steps += 1
        if age_jump:
            eng.mem.advance(age_jump)
        rep = eng.run_until_idle()
        outs = {k: list(v) for k, v in eng.outputs.items()}
        return eng, rep, outs

    _, clean_rep, outs_clean = run_one(None, age_jump=0.8 * deadline_s)
    eng_p, prot_rep, outs_prot = run_one(rber, age_jump=0.8 * deadline_s)
    eng_o, over_rep, outs_over = run_one(rber, refresh=False,
                                         age_jump=4.0 * retention_s)

    def match_fraction(a, b):
        total = hits = 0
        for k, toks in a.items():
            other = b.get(k, [])
            total += max(len(toks), len(other))
            hits += sum(1 for x, y in zip(toks, other) if x == y)
        return hits / max(total, 1)

    prot_match = match_fraction(outs_clean, outs_prot)
    over_match = match_fraction(outs_clean, outs_over)
    prot_rel = prot_rep["reliability"]
    over_rel = over_rep["reliability"]
    # the CI gate: corrected decode holds, unrefreshed decode degrades
    assert prot_match >= 0.95, \
        f"protected decode match {prot_match:.2%} under RBER {rber}"
    assert prot_rel["injection"]["uncorrectable_blocks"] == 0, prot_rel
    assert eng_p.kv.lifecycle.stats.scrubbed_pages > 0, \
        "scrub-on-read never fired in the protected arm"
    assert prot_rel["tiers"]["mrm"]["scrub_read_bytes"] > 0
    assert prot_rel["tiers"]["mrm"]["ecc_write_bytes"] > 0
    assert over_rel["injection"]["uncorrectable_blocks"] > 0, \
        "over-aged pages must report uncorrectable blocks"
    assert over_match < prot_match, (over_match, prot_match)
    assert over_match <= 0.9, \
        f"over-aged decode match {over_match:.2%} — no measurable degradation"
    # density lever: the domain split code must spend fewer check bits
    # than the uniform-strong baseline on every demoted-or-colder state
    dom = TierEcc(MRM_RRAM, "domain")
    uni = TierEcc(MRM_RRAM, "uniform")
    ladder = {}
    for state, frac in STATE_RETENTION_FRAC.items():
        r = MRM_RRAM.retention_s * frac
        od, ou = dom.overhead_for("kv", r), uni.overhead_for("kv", r)
        ladder[state] = {"domain": od, "uniform": ou,
                         "shrink": 1.0 - od / ou}
        if state != "hot":
            assert od < ou, f"{state}: domain {od} !< uniform {ou}"
    return {
        "arch": arch,
        "inject_rber": rber,
        "requests": len(prompts),
        "tokens_generated": clean_rep["tokens_generated"],
        "protected_match": prot_match,
        "overaged_match": over_match,
        "scrubbed_pages": eng_p.kv.lifecycle.stats.scrubbed_pages,
        "scrub_read_bytes": prot_rel["tiers"]["mrm"]["scrub_read_bytes"],
        "ecc_write_bytes": prot_rel["tiers"]["mrm"]["ecc_write_bytes"],
        "ecc_read_bytes": prot_rel["tiers"]["mrm"]["ecc_read_bytes"],
        "protected_injection": prot_rel["injection"],
        "overaged_injection": over_rel["injection"],
        "overaged_uncorrectable": over_rel["injection"]["uncorrectable_blocks"],
        "ecc_overhead_ladder": ladder,
    }


def replication(scenario="rag_storm", preset="smoke", threshold=2,
                copies=7, min_ttft_cut=0.40, ttft_slack=0.02) -> dict:
    """Predictive prefix replication A/B on the fleet simulator
    (DESIGN.md §13): the same scenario run reactive (no replication —
    demand migrations only) vs predictive (directory hit counts cross
    ``threshold`` → speculative ``REPLICATION_PUSH`` pre-places the group
    on the ``copies`` least-loaded non-owners over the shared fabric).

    Gates, on bit-identical decoded tokens across both arms:

    - ``min_ttft_cut`` > 0 (the rag_storm arm): predictive TTFT p95 must
      land at least that fraction below the reactive baseline — the
      herald-led fan-out hits warm owners instead of piling on one;
    - ``min_ttft_cut`` = 0 (the diurnal arm): predictive TTFT p95 must
      not regress beyond ``ttft_slack``;
    - speculative push bytes > 0 and demand-migration count strictly
      below the reactive baseline (pre-placement absorbs the pulls);
    - the fabric byte ledger balances (transfers == migrated +
      replicated bytes, enforced by ``FleetSim.check``) and the trace
      digest is bit-stable across a rerun *and* a shuffled submission
      order (the event queue, not submission order, fixes the timeline).
    """
    import random
    from dataclasses import replace as dc_replace

    from repro.serving.fleet_sim import FleetSim

    from experiments.scenarios import build

    def run_one(predictive: bool, shuffle_seed=None):
        sc = build(scenario, preset)
        cfg = sc.fleet()
        if predictive:
            cfg = dc_replace(cfg, replicate_threshold=threshold,
                             replicate_copies=copies)
        sim = FleetSim(cfg)
        rng = random.Random(sc.seed)
        if shuffle_seed is None:
            sc.submit_all(sim, rng)
        else:   # open-loop only: shuffled submission must not move events
            reqs = list(sc.generate(rng))
            random.Random(shuffle_seed).shuffle(reqs)
            for r in reqs:
                sim.submit(r)
        rep = sim.run(max_events=20_000_000)
        sim.check()
        return rep

    base = run_one(False)
    pred = run_one(True)
    rerun = run_one(True)
    shuffled = run_one(True, shuffle_seed=1234)
    assert pred["trace"]["digest"] == rerun["trace"]["digest"], \
        f"{scenario}: predictive trace digest unstable across reruns"
    assert pred["trace"]["digest"] == shuffled["trace"]["digest"], \
        f"{scenario}: trace digest moved under submission shuffle"
    bf, pf = base["fleet"], pred["fleet"]
    assert pf["decoded_tokens"] == bf["decoded_tokens"], \
        (pf["decoded_tokens"], bf["decoded_tokens"])
    rp = pred["replication"]
    assert rp["replicated_bytes"] > 0, "no speculative push bytes metered"
    assert pf["migrations"] < bf["migrations"], \
        f"demand migrations {pf['migrations']} !< baseline {bf['migrations']}"
    ttft_base = base["slo"]["ttft"]["p95"]
    ttft_pred = pred["slo"]["ttft"]["p95"]
    ttft_cut = 1.0 - ttft_pred / ttft_base
    if min_ttft_cut > 0:
        assert ttft_cut >= min_ttft_cut, \
            f"{scenario}: TTFT p95 cut {ttft_cut:.2%} < {min_ttft_cut:.0%}"
    else:
        assert ttft_cut >= -ttft_slack, \
            f"{scenario}: predictive regressed TTFT p95 by {-ttft_cut:.2%}"
    shards = pred["directory"]
    assert shards["delta_batches"] <= shards["delta_ops"]
    return {
        "scenario": f"{scenario}/{preset}+replication",
        "threshold": threshold,
        "copies": copies,
        "ttft_p95_reactive_s": ttft_base,
        "ttft_p95_predictive_s": ttft_pred,
        "ttft_p95_cut": ttft_cut,
        "ttft_p99_reactive_s": base["slo"]["ttft"]["p99"],
        "ttft_p99_predictive_s": pred["slo"]["ttft"]["p99"],
        "decoded_tokens": pf["decoded_tokens"],
        "migrations_reactive": bf["migrations"],
        "migrations_predictive": pf["migrations"],
        "replication_pushes": rp["pushes_scheduled"],
        "replications": rp["replications"],
        "replicated_bytes": rp["replicated_bytes"],
        "pushes_deferred": rp["pushes_deferred"],
        "pushes_abandoned": rp["pushes_abandoned"],
        # every fabric byte is exactly one demand or speculative byte
        "ledger_imbalance": pred["fabric"]["bytes"]
        - pf["migrated_bytes"] - rp["replicated_bytes"],
        "fabric": pred["fabric"],
        "directory_shards": shards,
        "reuse_frac_reactive": bf["reuse_frac"],
        "reuse_frac_predictive": pf["reuse_frac"],
        "trace_digest": pred["trace"]["digest"],
    }


def run_replication(csv=True):
    """The ``replication`` benchmark suite (its own CI leg): reactive vs
    predictive on the herald-led rag_storm fan-out (hard >= 40% TTFT p95
    cut) and the diurnal tenant mix (migration elimination at flat TTFT),
    both persisted to BENCH_fleet.json alongside the fleet trajectory."""
    from repro.core.trajectory import persist_trajectory

    out = {}
    for key, kw in (
            ("rag_storm", dict(scenario="rag_storm", threshold=2, copies=7,
                               min_ttft_cut=0.40)),
            ("diurnal", dict(scenario="diurnal", threshold=4, copies=2,
                             min_ttft_cut=0.0))):
        t0 = time.perf_counter()
        entry = replication(**kw)
        dt = (time.perf_counter() - t0) * 1e6
        out[key] = entry
        persist_trajectory("BENCH_fleet.json", entry, key="scenario",
                           ignore=("at",))
        if csv:
            print(f"serving_sim/repl_{key}_ttft_p95_cut,{dt:.1f},"
                  f"{entry['ttft_p95_cut']:.4f}")
            print(f"serving_sim/repl_{key}_migrations,{dt:.1f},"
                  f"{entry['migrations_predictive']}")
            print(f"serving_sim/repl_{key}_migrations_reactive,{dt:.1f},"
                  f"{entry['migrations_reactive']}")
            print(f"serving_sim/repl_{key}_replicated_gb,{dt:.1f},"
                  f"{entry['replicated_bytes'] / 1e9:.4f}")
            print(f"serving_sim/repl_{key}_pushes_deferred,{dt:.1f},"
                  f"{entry['pushes_deferred']}")
    return out


def _persist_reliability_trajectory(entry: dict) -> None:
    """Append the reliability sweep result to BENCH_reliability.json —
    the CI artifact tracking decode-match / scrub / uncorrectable metrics
    run over run (deduped on identical metrics)."""
    _persist_trajectory("BENCH_reliability.json", entry)


def _persist_trajectory(filename: str, entry: dict) -> None:
    # shared with experiments/run_fleet.py (BENCH_fleet.json) — one
    # dedupe-on-identical-metrics rule for every trajectory file
    from repro.core.trajectory import persist_trajectory
    persist_trajectory(filename, entry, key="arch")


def run_reliability(csv=True):
    """The ``reliability`` benchmark suite (its own CI leg — the fault-
    injection gate is an A/B over three full engine runs and stays out of
    the smoke-path serving suite)."""
    t0 = time.perf_counter()
    rel = reliability()
    dt = (time.perf_counter() - t0) * 1e6
    _persist_reliability_trajectory(rel)
    if csv:
        print(f"serving_sim/reliability_protected_match,{dt:.1f},"
              f"{rel['protected_match']:.4f}")
        print(f"serving_sim/reliability_overaged_match,{dt:.1f},"
              f"{rel['overaged_match']:.4f}")
        print(f"serving_sim/reliability_scrubbed_pages,{dt:.1f},"
              f"{rel['scrubbed_pages']}")
        print(f"serving_sim/reliability_uncorrectable,{dt:.1f},"
              f"{rel['overaged_uncorrectable']}")
        for state, row in rel["ecc_overhead_ladder"].items():
            print(f"serving_sim/reliability_ecc_shrink_{state},{dt:.1f},"
                  f"{row['shrink']:.4f}")
    return {"reliability": rel}


def run(csv=True):
    t0 = time.perf_counter()
    out = compute()
    dt = (time.perf_counter() - t0) * 1e6
    if csv:
        for k, v in out.items():
            print(f"serving_sim/{k}_refresh_overhead,{dt:.1f},{v['refresh_overhead']:.4f}")
            print(f"serving_sim/{k}_energy_per_token,{dt:.1f},{v['energy_per_token_j']:.3e}")
    t0 = time.perf_counter()
    fleet = cluster_sweep()
    dt = (time.perf_counter() - t0) * 1e6
    out.update(fleet)
    if csv:
        for k, v in fleet.items():
            print(f"serving_sim/{k}_fleet_tokens_per_s,{dt:.1f},{v['fleet_tokens_per_s']:.4f}")
            print(f"serving_sim/{k}_pressure_events,{dt:.1f},{v['pressure_events']}")
            print(f"serving_sim/{k}_dropped_allocs,{dt:.1f},{v['dropped_allocs']}")
    # prefix reuse must be real compute savings for every resumption
    # kind: attention KV pages, SSM state pages, the hybrid union; the
    # hybrid sweep runs a denser fan-out: a point stack resumes only at
    # page boundaries
    for key, reuse_arch, wkw in (
            ("prefix_reuse", "deepseek-7b", {}),
            ("prefix_reuse_ssm", "mamba2-2.7b", {}),
            ("prefix_reuse_hybrid", "hymba-1.5b",
             dict(n_system=8, turns=3, fanout=8))):
        t0 = time.perf_counter()
        reuse = prefix_reuse(reuse_arch, **wkw)
        dt = (time.perf_counter() - t0) * 1e6
        out[key] = reuse
        if csv:
            tag = key.replace("prefix_reuse", "prefix")
            print(f"serving_sim/{tag}_hit_rate,{dt:.1f},{reuse['prefix_hit_rate']:.4f}")
            print(f"serving_sim/{tag}_tokens_reused,{dt:.1f},{reuse['tokens_reused']}")
            print(f"serving_sim/{tag}_prefill_cut,{dt:.1f},{reuse['prefill_cut']:.4f}")
            if reuse["kv_write_cut"] is not None:
                print(f"serving_sim/{tag}_kv_write_cut,{dt:.1f},{reuse['kv_write_cut']:.4f}")
            print(f"serving_sim/{tag}_ttft_p50_s,{dt:.1f},{reuse['ttft_p50_s']:.6f}")
    # sub-page tails: boundary-straddling prefixes must beat the
    # page-aligned cut strictly (DESIGN.md §9)
    t0 = time.perf_counter()
    tail = tail_reuse()
    dt = (time.perf_counter() - t0) * 1e6
    out["tail_reuse"] = tail
    if csv:
        print(f"serving_sim/tail_prefill_cut,{dt:.1f},{tail['prefill_cut']:.4f}")
        print(f"serving_sim/tail_prefill_cut_page_aligned,{dt:.1f},"
              f"{tail['prefill_cut_page_aligned']:.4f}")
        print(f"serving_sim/tail_hits,{dt:.1f},{tail['tail_hits']}")
        print(f"serving_sim/tail_copy_bytes,{dt:.1f},{tail['tail_copy_bytes']:.0f}")
    for key, fleet_arch, seed_tail in (("fleet_reuse", "deepseek-7b", 16),
                                       ("fleet_reuse_ssm", "mamba2-2.7b", 0)):
        t0 = time.perf_counter()
        fleet_r = fleet_reuse(fleet_arch, seed_tail_tokens=seed_tail)
        dt = (time.perf_counter() - t0) * 1e6
        out[key] = fleet_r
        if csv:
            tag = key.replace("fleet_reuse", "fleet")
            print(f"serving_sim/{tag}_prefill_cut,{dt:.1f},{fleet_r['prefill_cut']:.4f}")
            print(f"serving_sim/{tag}_cross_replica_hits,{dt:.1f},{fleet_r['cross_replica_hits']}")
            print(f"serving_sim/{tag}_migrations,{dt:.1f},{fleet_r['migrations']}")
            print(f"serving_sim/{tag}_migration_gb,{dt:.1f},{fleet_r['migration_bytes'] / 1e9:.4f}")
            print(f"serving_sim/{tag}_ledger_imbalance,{dt:.1f},{fleet_r['ledger_imbalance']}")
    return out


if __name__ == "__main__":
    import json
    print(json.dumps(run(csv=False), indent=1, default=float))
