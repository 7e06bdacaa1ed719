"""Paged-attention kernel microbench: grouped grid vs the ungrouped
PR 6 gather on sparse page tables, per-config timings, and a persisted
trajectory.

For each (page_size, head_dim) geometry the bench builds a ragged batch
on a *sparse* table (interior null slots — the shape radix splices and
windowed decode produce) and runs

- the **grouped, null-skipping grid** under a handful of
  (block_q, block_kv, num_buffers) configs (timed per config), and
- the **ungrouped baseline** (``skip_blocks=False``: one full-width
  gather per sequence, nulls masked in-register — the PR 6 behavior),

asserting the outputs agree with the jnp reference (fp32) to a few ulp
(a block of pages is summed at once where the reference scans page by
page) — and metering the *achieved page-read
bytes* of each grid with the kernel's host-side gather replica
(``kernel.pages_gathered``). On sparse tables the grouped grid must read
strictly less; smoke.sh gates that from the persisted trajectory.

Results append to ``BENCH_kernels.json`` at the repo root:
``{"entries": [{at, arch, cases: [{page_size, head_dim, read-bytes per
grid, configs: [{block_q, block_kv, num_buffers, time_us}, ...]}]}]}``.
Timings are wall-clock per call (interpret mode off TPU — ranking, not
absolute numbers; the arch field says which kind a row is).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

GEOMETRIES = ((8, 16), (16, 16), (32, 16))
CONFIGS = ((8, 4, 2), (16, 8, 2), (16, 8, 4), (32, 16, 3))

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_kernels.json")


def _sparse_case(ps: int, D: int, *, Hkv: int = 2, G: int = 2,
                 seqs: int = 3, width: int = 8, seed: int = 0):
    """Ragged batch over mostly-null tables: every other slot of each
    row is the null page, query lengths mix decode and extend."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    W = width
    P = 1 + seqs * W
    kv = jnp.asarray(rng.standard_normal((P, ps, 2 * Hkv, D)),
                     jnp.float32).at[0].set(0.0)
    tbl = np.zeros((seqs, W), np.int32)
    kvl = np.zeros((seqs,), np.int32)
    q_lens = []
    for s in range(seqs):
        used = W - s % 3
        for j in range(used):
            if j % 2 == 1:
                continue                      # interior null slot
            tbl[s, j] = 1 + s * W + j
        kvl[s] = used * ps - (s % ps)
        q_lens.append(1 + (s * 7) % (2 * ps))  # decode + ragged extend
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((int(cu[-1]), Hkv * G, D)),
                    jnp.float32)
    return (q, kv, jnp.asarray(tbl), jnp.asarray(cu), jnp.asarray(kvl),
            int(max(q_lens)))


def _time_call(fn, repeats: int = 3) -> float:
    fn()                                       # compile / warm the cache
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats * 1e6


def bench_case(ps: int, D: int, repeats: int = 3) -> dict:
    """One geometry: grouped configs + ungrouped baseline, agreement
    against the reference, achieved page-read bytes per grid."""
    import jax.numpy as jnp

    from repro.kernels.paged_attention.kernel import pages_gathered
    from repro.kernels.paged_attention.ops import ragged_paged_attention
    from repro.kernels.paged_attention.ref import ragged_paged_attention_ref

    q, kv, tbl, cu, kvl, max_q = _sparse_case(ps, D)
    scale = 1.0 / D ** 0.5
    ref = np.asarray(ragged_paged_attention_ref(q, kv, tbl, cu, kvl,
                                                scale=scale))
    atol = 4 * float(np.spacing(np.abs(ref).max()))
    page_bytes = ps * kv.shape[2] * D * kv.dtype.itemsize

    def call(**kw):
        return ragged_paged_attention(
            q, kv, tbl, cu, kvl, scale=scale, max_q_len=max_q,
            backend="pallas", **kw).block_until_ready()

    configs = []
    for bq, bkv, nb in CONFIGS:
        out = call(block_q=bq, block_kv=bkv, num_buffers=nb)
        np.testing.assert_allclose(
            np.asarray(out), ref, rtol=0, atol=atol,
            err_msg=f"grouped grid ps={ps} cfg=({bq},{bkv},{nb}) diverged "
                    f"from ref")
        configs.append({
            "block_q": bq, "block_kv": bkv, "num_buffers": nb,
            "time_us": _time_call(
                lambda: call(block_q=bq, block_kv=bkv, num_buffers=nb),
                repeats),
        })
    base = call(skip_blocks=False)
    np.testing.assert_allclose(
        np.asarray(base), ref, rtol=0, atol=atol,
        err_msg=f"ungrouped baseline ps={ps} diverged from ref")
    time_base = _time_call(lambda: call(skip_blocks=False), repeats)

    pages_grouped = pages_gathered(tbl, cu, kvl, page_size=ps,
                                   max_q_len=max_q, block_q=CONFIGS[0][0])
    pages_full = pages_gathered(tbl, cu, kvl, page_size=ps,
                                max_q_len=max_q, skip_blocks=False)
    assert 0 < pages_grouped < pages_full, (pages_grouped, pages_full)
    return {
        "page_size": ps,
        "head_dim": D,
        "seqs": int(tbl.shape[0]),
        "table_width": int(tbl.shape[1]),
        "query_rows": int(q.shape[0]),
        "pages_read_grouped": pages_grouped,
        "pages_read_ungrouped": pages_full,
        "kernel_read_bytes_grouped": pages_grouped * page_bytes,
        "kernel_read_bytes_ungrouped": pages_full * page_bytes,
        "read_bytes_cut": 1.0 - pages_grouped / pages_full,
        "time_us_ungrouped": time_base,
        "configs": configs,
    }


def _persist(entry: dict) -> None:
    data = {"entries": []}
    if os.path.exists(BENCH_PATH):
        try:
            with open(BENCH_PATH) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {"entries": []}
    data.setdefault("entries", []).append(entry)
    with open(BENCH_PATH, "w") as f:
        json.dump(data, f, indent=1, default=float)
        f.write("\n")


def run(csv: bool = True) -> dict:
    import jax

    from repro.kernels.paged_attention.tune import _arch

    cases = []
    for ps, D in GEOMETRIES:
        t0 = time.perf_counter()
        case = bench_case(ps, D)
        dt = (time.perf_counter() - t0) * 1e6
        cases.append(case)
        if csv:
            tag = f"kernel_bench/ps{ps}_d{D}"
            print(f"{tag}_read_bytes_grouped,{dt:.1f},"
                  f"{case['kernel_read_bytes_grouped']}")
            print(f"{tag}_read_bytes_ungrouped,{dt:.1f},"
                  f"{case['kernel_read_bytes_ungrouped']}")
            print(f"{tag}_read_cut,{dt:.1f},{case['read_bytes_cut']:.4f}")
            best = min(case["configs"], key=lambda c: c["time_us"])
            print(f"{tag}_best_config,{dt:.1f},"
                  f"bq{best['block_q']}-bkv{best['block_kv']}"
                  f"-nb{best['num_buffers']}")
    entry = {"at": time.time(), "arch": _arch(),
             "backend": jax.default_backend(), "cases": cases}
    _persist(entry)
    return entry


if __name__ == "__main__":
    print(json.dumps(run(csv=False), indent=1, default=float))
