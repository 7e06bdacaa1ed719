"""Serving entry point: a model computes real tokens while the MRM control
plane meters the deployment-size memory system. By default the model is
the ``reduced()`` CPU-size sibling of --arch; --layers N serves the
published widths instead, cut to the first N layers (``at_depth``), as on
the chip. With --replicas N a :class:`ClusterFrontend` fans requests
across N engine replicas (fleet prefix-directory routing, shared
simulated clock, aggregated fleet report); replica i holds its own copy
of the weights and its page pool on ``jax.devices()[i % devices]``.
--shared-prefix-tokens K makes the generated traffic share a K-token
prompt head, exercising radix prefix reuse end to end;
--migrate-prefixes additionally lets the directory *move* a hot prefix
(pages + compute snapshot) to a less-loaded replica at --interconnect-gbps
instead of queueing every match on its owner.

Every architecture in the pool serves — attention, MLA, SSM and hybrid —
including chunked prefill and prefix *compute* reuse (positional ring
snapshots vs page-boundary point snapshots of recurrent state;
DESIGN.md §8). Try --arch mamba2-2.7b or --arch hymba-1.5b with
--shared-prefix-tokens 32.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b \
      --requests 8 --max-new 16 --kv-tier mrm_rram --weight-tier mrm_rram \
      --replicas 2 --chunk-tokens 32 --kv-policy evict-lru \
      --shared-prefix-tokens 32 --radix-hot-tier auto

Runs in this one process and starts no other: a chip belongs to one
process at a time.
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import jax
import numpy as np

REPO_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``<repo>/.jax_cache`` — a fixed path, because the path is part of the
    cache key."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO_ROOT / ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def model_config(args):
    """(compute config, published config) for --arch: ``reduced()`` by
    default, the published widths cut to --layers layers when given."""
    from repro.configs import at_depth, get_config, reduced

    full = get_config(args.arch)
    cfg = at_depth(full, args.layers) if args.layers else reduced(full)
    return cfg, full


def replica_params(params, n: int) -> list:
    """Replica i's weights, committed to ``jax.devices()[i % devices]``;
    replicas that share a device share one copy."""
    devices = jax.devices()
    copies = [jax.device_put(params, d) for d in devices[:n]]
    return [copies[i % len(copies)] for i in range(n)]


def build_engine(args, cfg, full, params):
    from repro.core.memclass import get_technology
    from repro.core.simulator import MemorySystem
    from repro.serving import EngineConfig, ServeEngine

    tiers = {"hbm": (get_technology("hbm3e"), int(args.hbm_gb * 2**30))}
    for t in {args.weight_tier, args.kv_tier} - {"hbm"}:
        tiers[t] = (get_technology(t), int(args.mrm_gb * 2**30))
    if args.spill_tier and args.spill_tier not in tiers:
        tiers[args.spill_tier] = (get_technology(args.spill_tier),
                                  int(args.mrm_gb * 2**30))
    mem = MemorySystem(tiers, ecc_profile=args.ecc_profile,
                       service_refresh=not args.no_refresh)
    return ServeEngine(
        cfg, params, mem,
        EngineConfig(max_slots=args.slots, max_cache_len=128,
                     weight_tier=args.weight_tier, kv_tier=args.kv_tier,
                     page_tokens=args.page_tokens,
                     expected_session_s=args.session_s,
                     chunk_tokens=args.chunk_tokens,
                     kv_pressure_policy=args.kv_policy,
                     kv_spill_tier=args.spill_tier,
                     prefix_caching=not args.no_prefix_caching,
                     tail_copy=args.tail_copy == "on",
                     paged_kernel=args.paged_kernel == "on",
                     radix_hot_threshold=args.radix_hot_threshold,
                     radix_hot_tier=args.radix_hot_tier,
                     radix_cold_ttl_s=args.radix_cold_ttl,
                     demote_on_pressure=args.demote_on_pressure,
                     inject_rber=args.inject_rber,
                     inject_seed=args.seed,
                     abandon_after_s=args.abandon_after),
        account_cfg=full)


def build_frontend(args, engines):
    from repro.serving import ClusterFrontend

    return ClusterFrontend(engines,
                           migrate_prefixes=args.migrate_prefixes,
                           interconnect_gbps=args.interconnect_gbps,
                           clock_mode=args.clock,
                           fabric_bisection_gbps=args.fabric_gbps,
                           replicate_threshold=args.replicate_threshold,
                           replicate_copies=args.replicate_copies,
                           directory_shards=args.directory_shards)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve the published widths cut to the first N "
                         "layers (whole periods of the layer pattern); "
                         "default: the reduced() CPU-size model")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--weight-tier", default="mrm_rram")
    ap.add_argument("--kv-tier", default="mrm_rram")
    ap.add_argument("--hbm-gb", type=float, default=64)
    ap.add_argument("--mrm-gb", type=float, default=512)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--session-s", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked prefill piece size (None = one maximal "
                         "chunk per prompt on the same unpadded path; "
                         "every mixer family supports chunking)")
    ap.add_argument("--kv-policy", default="evict-lru",
                    choices=("none", "evict-lru", "spill", "recompute"))
    ap.add_argument("--spill-tier", default=None,
                    help="colder tier for the 'spill' pressure policy")
    ap.add_argument("--sessions", type=int, default=3,
                    help="distinct session keys for affinity routing")
    ap.add_argument("--page-tokens", type=int, default=32,
                    help="KV page size in tokens (radix match granularity)")
    ap.add_argument("--no-prefix-caching", action="store_true",
                    help="disable the radix prefix tree (cold baseline; "
                         "the prompt layout is unpadded either way)")
    ap.add_argument("--paged-kernel", choices=("on", "off"), default="on",
                    help="run extend+decode in place on the paged compute "
                         "plane — universal across families: attention/MLA "
                         "on KV pages, SSM/hybrid on pooled point-state "
                         "pages (zero-copy prefix hits, kernel-metered "
                         "tier reads; DESIGN.md §10)")
    ap.add_argument("--tail-copy", choices=("on", "off"), default="on",
                    help="sub-page tail reuse: copy the shared mid-page "
                         "tail into the borrower's page and resume prefill "
                         "from the exact token boundary (DESIGN.md §9)")
    ap.add_argument("--demote-on-pressure", action="store_true",
                    help="under eviction pressure, demote hot prefixes "
                         "back to short retention (metered reprogram) "
                         "before leaf eviction may reach them")
    ap.add_argument("--shared-prefix-tokens", type=int, default=0,
                    help="generated prompts share a head of this many "
                         "tokens (shared system prompt traffic)")
    ap.add_argument("--radix-hot-threshold", type=int, default=4,
                    help="reuse count promoting a prefix to long retention")
    ap.add_argument("--radix-hot-tier", default=None,
                    help="tier for hot prefixes ('auto' = placement solve)")
    ap.add_argument("--radix-cold-ttl", type=float, default=None,
                    help="idle seconds before a cold prefix leaf decays")
    ap.add_argument("--migrate-prefixes", action="store_true",
                    help="fleet prefix directory migrates a hot prefix to "
                         "a less-loaded replica instead of queueing on the "
                         "owner (metered inter-replica transfer)")
    ap.add_argument("--ecc-profile", choices=("off", "uniform", "domain"),
                    default="off",
                    help="reliability plane (DESIGN.md §11): meter ECC "
                         "check bits per tier — 'uniform' sizes one strict "
                         "code per retention point, 'domain' additionally "
                         "lets KV/state pages use the exponent-protected / "
                         "mantissa-relaxed split codeword (denser on "
                         "demoted/cold pages); 'off' meters nothing")
    ap.add_argument("--inject-rber", type=float, default=None,
                    help="inject age-driven bit flips into paged KV/state "
                         "pages: a page exactly at its programmed retention "
                         "sees this raw bit error rate; correction/scrub "
                         "behavior follows --ecc-profile (DESIGN.md §11)")
    ap.add_argument("--no-refresh", action="store_true",
                    help="disable retention-deadline servicing (pages age "
                         "past retention unrefreshed) — the reliability "
                         "gate's degradation A/B arm")
    ap.add_argument("--clock", choices=("lockstep", "event"),
                    default="lockstep",
                    help="cluster clock discipline (DESIGN.md §12): "
                         "'lockstep' advances every replica together each "
                         "frontend step (the PR 3-8 compat driver); 'event' "
                         "drains a priority event queue so replicas advance "
                         "independently and idle ones jump their clocks")
    ap.add_argument("--abandon-after", type=float, default=None,
                    help="seconds a request may wait queued before the "
                         "scheduler abandons it (None = wait forever)")
    ap.add_argument("--interconnect-gbps", type=float, default=50.0,
                    help="per-replica NIC link bandwidth in GBYTES/s — "
                         "the same unit as the memclass tier "
                         "read_bw_gbps/write_bw_gbps fields (the "
                         "prefix-migration cost model)")
    ap.add_argument("--fabric-gbps", type=float, default=None,
                    help="shared-fabric bisection bandwidth in GBYTES/s "
                         "(DESIGN.md §13); transfers queue on donor "
                         "up-links, receiver down-links and "
                         "floor(fabric/link) core channels (default: "
                         "half-bisection, link * replicas//2)")
    ap.add_argument("--replicate-threshold", type=int, default=None,
                    help="fleet-wide directory hits after which a prefix "
                         "is speculatively pushed to the least-loaded "
                         "non-owners (DESIGN.md §13; default: reactive "
                         "demand migration only)")
    ap.add_argument("--replicate-copies", type=int, default=1,
                    help="extra owners the predictive replicator "
                         "maintains for a hot prefix")
    ap.add_argument("--directory-shards", type=int, default=8,
                    help="hash shards the fleet prefix directory spreads "
                         "its digest keys across (load-balance counters "
                         "land in the report)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    configure_compile_cache()

    from repro.models import init_params

    cfg, full = model_config(args)
    params = init_params(cfg, jax.random.key(args.seed))
    engines = [build_engine(args, cfg, full, p)
               for p in replica_params(params, max(args.replicas, 1))]
    rng = np.random.default_rng(args.seed)

    if cfg.n_codebooks > 1:
        shared_head = [list(rng.integers(0, cfg.vocab_size, cfg.n_codebooks))
                       for _ in range(args.shared_prefix_tokens)]
    else:
        shared_head = list(rng.integers(2, cfg.vocab_size,
                                        args.shared_prefix_tokens))

    def gen_prompt():
        n = rng.integers(8, 48)
        if cfg.n_codebooks > 1:
            tail = [list(rng.integers(0, cfg.vocab_size, cfg.n_codebooks))
                    for _ in range(n)]
        else:
            tail = list(rng.integers(2, cfg.vocab_size, n))
        return shared_head + tail

    if len(engines) == 1:
        eng = engines[0]
        for _ in range(args.requests):
            eng.submit(gen_prompt(), max_new_tokens=args.max_new)
        rep = eng.run_until_idle()
    else:
        fe = build_frontend(args, engines)
        for i in range(args.requests):
            fe.submit(gen_prompt(), max_new_tokens=args.max_new,
                      session_key=f"session-{i % max(args.sessions, 1)}")
        rep = fe.run_until_idle()
    print(json.dumps(rep, indent=1, default=float))
    return rep


if __name__ == "__main__":
    main()
