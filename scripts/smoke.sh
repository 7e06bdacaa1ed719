#!/usr/bin/env bash
# Smoke check: tier-1 tests + one fast serving benchmark with a JSON
# trajectory + the documented examples. Run from the repo root:
#   bash scripts/smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== examples (the README quickstart entry points must keep running) =="
python examples/quickstart.py > /dev/null
python examples/serve_batched_mrm.py > /dev/null

echo "== serving benchmark (fast) =="
python -m benchmarks.run serving --json /tmp/smoke_serving.json
python - <<'EOF'
import json
rep = json.load(open("/tmp/smoke_serving.json"))
assert not rep["failures"], rep["failures"]
fleet = rep["suites"]["serving"]["replicas_2"]
assert fleet["dropped_allocs"] == 0, fleet
# prefix reuse must be real compute savings for EVERY resumption kind
# (DESIGN.md §8): attention KV pages, SSM state pages, hybrid union
for key in ("prefix_reuse", "prefix_reuse_ssm", "prefix_reuse_hybrid"):
    reuse = rep["suites"]["serving"][key]
    assert reuse["prefill_cut"] >= 0.30, (key, reuse)
    if reuse["kv_write_cut"] is not None:
        assert reuse["kv_write_cut"] >= 0.30, (key, reuse)
# sub-page tails (DESIGN.md §9): boundary-straddling prefixes must cut
# strictly more prefill tokens than the page-aligned matcher, with the
# tail copies actually metered — a tail-reuse regression fails the build
tr = rep["suites"]["serving"]["tail_reuse"]
assert tr["prefill_cut"] > tr["prefill_cut_page_aligned"], tr
assert tr["tail_hits"] > 0 and tr["tail_copy_bytes"] > 0, tr
# fleet-level reuse: the prefix directory + cross-replica migration must
# cut fleet prefill tokens >= 20% vs the per-replica radix baseline, with
# real metered interconnect traffic and balanced pressure ledgers — a
# cross-replica reuse regression fails the build here. The SSM variant
# moves recurrent-state pages over the wire (no KV byte stream).
for key in ("fleet_reuse", "fleet_reuse_ssm"):
    fr = rep["suites"]["serving"][key]
    assert fr["prefill_cut"] >= 0.20, (key, fr)
    assert fr["ledger_imbalance"] == 0, (key, fr)
    assert fr["cross_replica_hits"] > 0, (key, fr)
    assert fr["migration_bytes"] > 0, (key, fr)
    assert fr["dropped_allocs"] == 0, (key, fr)
reuse = rep["suites"]["serving"]["prefix_reuse"]
fr = rep["suites"]["serving"]["fleet_reuse"]
print("smoke OK:", {k: fleet[k] for k in ("finished", "tokens_generated",
                                          "pressure_events", "dropped_allocs")})
print("prefix reuse:", {k: round(reuse[k], 4) for k in
                        ("prefix_hit_rate", "prefill_cut", "kv_write_cut")})
print("prefix reuse (ssm/hybrid):",
      {k: round(rep["suites"]["serving"][k]["prefill_cut"], 4)
       for k in ("prefix_reuse_ssm", "prefix_reuse_hybrid")})
print("tail reuse:", {k: round(tr[k], 4) for k in
                      ("prefill_cut", "prefill_cut_page_aligned",
                       "tail_hits", "tail_tokens_copied")})
print("fleet reuse:", {k: round(fr[k], 4) for k in
                       ("prefill_cut", "cross_replica_hit_rate",
                        "migrations", "migration_bytes")})
print("fleet reuse (ssm):",
      {k: round(rep["suites"]["serving"]["fleet_reuse_ssm"][k], 4) for k in
       ("prefill_cut", "cross_replica_hit_rate", "migration_bytes")})
EOF

echo "== predictive replication A/B (reactive vs predictive fleet plane) =="
python -m benchmarks.run replication --json /tmp/smoke_replication.json
python - <<'EOF'
import json
rep = json.load(open("/tmp/smoke_replication.json"))
assert not rep["failures"], rep["failures"]
# DESIGN.md §13 gates: the herald-led rag_storm fan-out must cut TTFT
# p95 >= 40% vs the reactive baseline at bit-identical decoded tokens,
# with nonzero speculative push bytes, strictly fewer demand migrations,
# and a fabric byte ledger that balances exactly (every byte is one
# demand migration or one speculative push — zero imbalance)
for key, arm in rep["suites"]["replication"].items():
    assert arm["replicated_bytes"] > 0, (key, arm)
    assert arm["migrations_predictive"] < arm["migrations_reactive"], \
        (key, arm)
    assert arm["ledger_imbalance"] == 0, (key, arm)
rs = rep["suites"]["replication"]["rag_storm"]
assert rs["ttft_p95_cut"] >= 0.40, rs
di = rep["suites"]["replication"]["diurnal"]
assert di["ttft_p95_cut"] >= -0.02, di
print("replication:", {k: {"ttft_p95_cut": round(a["ttft_p95_cut"], 4),
                           "migrations": (a["migrations_reactive"],
                                          a["migrations_predictive"]),
                           "replicated_gb": round(a["replicated_bytes"] / 1e9,
                                                  2)}
                       for k, a in rep["suites"]["replication"].items()})
EOF

echo "== kernel bench (grouped grid vs ungrouped baseline) =="
python -m benchmarks.run kernel_bench --json /tmp/smoke_kernels.json
python - <<'EOF'
import json
rep = json.load(open("/tmp/smoke_kernels.json"))
assert not rep["failures"], rep["failures"]
# the grouped, null-skipping grid must read strictly fewer page bytes
# than the ungrouped (PR 6) gather on sparse page tables — at bit-equal
# outputs (asserted inside the bench) — for every geometry; the same
# entry lands in BENCH_kernels.json as the persisted trajectory
entry = rep["suites"]["kernel_bench"]
for case in entry["cases"]:
    g, u = (case["kernel_read_bytes_grouped"],
            case["kernel_read_bytes_ungrouped"])
    assert 0 < g < u, case
traj = json.load(open("BENCH_kernels.json"))
assert traj["entries"], "kernel-bench trajectory must persist"
print("kernel bench:", [
    {"ps": c["page_size"], "read_cut": round(c["read_bytes_cut"], 4)}
    for c in entry["cases"]])
EOF
