"""Block-shape / DMA-depth autotuning for the paged-attention kernel.

The grouped kernel has three free parameters — ``block_q`` (query rows
per tile), ``block_kv`` (page-table slots per block), ``num_buffers``
(DMA pipeline depth, 2–4) — whose best values depend on the page
geometry (page_size × head_dim fixes the VMEM slab a buffer holds) and
the accelerator generation, not on the workload. So they are tuned once
per ``(page_size, head_dim, arch)`` and cached:

* ``best_config(ps, D)`` — cheap lookup: explicit cache entry (from a
  prior ``autotune`` run, in-process or loaded from a JSON table) else a
  static heuristic default. Never runs the kernel.
* ``autotune(ps, D, ...)`` — sweeps a small candidate grid with the real
  kernel on synthetic ragged data, times each config, caches the winner,
  and optionally persists the table so later processes skip the sweep.

The heuristic default keeps the resident VMEM footprint
(``num_buffers`` KV slabs + fp32 accumulators) small enough for every
geometry the configs in this repo produce; the sweep exists for real
TPUs where deeper pipelines win once pages are large enough to hide
latency behind compute.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import asdict, dataclass
from typing import Dict, Optional, Tuple

# candidate sweep: q rows per tile x table slots per block x DMA depth
CANDIDATE_BLOCK_Q = (8, 16, 32)
CANDIDATE_BLOCK_KV = (4, 8, 16)
CANDIDATE_BUFFERS = (2, 3, 4)


@dataclass(frozen=True)
class KernelConfig:
    block_q: int = 16
    block_kv: int = 8
    num_buffers: int = 2


# configs timed on the chip (``benchmarks/attention_chip.py``), by
# (page_size, head_dim, arch): on a TPU v5e with 32-token pages of head
# dim 128, 16-page blocks read GQA 32/8 decode rounds fastest (the
# kernel caps the block of a larger page, MHA's, to its VMEM budget,
# where 4 pages read fastest), and 32-row query tiles read a 96-row
# extend 6 times faster than 16-row ones
_CACHE: Dict[Tuple[int, int, str], KernelConfig] = {
    (32, 128, "tpu-v5-lite"): KernelConfig(block_q=32, block_kv=16,
                                           num_buffers=2),
}


def _arch() -> str:
    """Device kind the tuned numbers belong to (``cpu`` for interpret-mode
    timings, so they never masquerade as TPU results). A failure to list
    devices propagates: there is no device to tune for."""
    import jax

    return jax.devices()[0].device_kind.replace(" ", "-").lower()


def best_config(page_size: int, head_dim: int,
                arch: Optional[str] = None) -> KernelConfig:
    """Cached best config for this geometry; heuristic default if the
    geometry was never tuned."""
    key = (int(page_size), int(head_dim), arch or _arch())
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    # heuristic: bigger pages already amortize DMA setup, so keep the
    # pipeline shallow; small pages want more in flight.
    if page_size and page_size <= 8:
        return KernelConfig(block_q=16, block_kv=8, num_buffers=3)
    return KernelConfig()


def resolve_config(page_size: int, head_dim: int, max_q_len: int,
                   block_q: Optional[int] = None,
                   block_kv: Optional[int] = None,
                   num_buffers: Optional[int] = None) -> KernelConfig:
    """Effective config for one launch: explicit overrides win, the rest
    comes from the cache; a tile never exceeds max_q rows, and the depth
    is 2-4. ``block_kv`` is not cut to the table width: the kernel sums
    each block of pages at once, so its result may not depend on how far
    a table is padded."""
    base = best_config(page_size, head_dim)
    bq = max(1, min(int(block_q or base.block_q), max(1, int(max_q_len))))
    bkv = max(1, int(block_kv or base.block_kv))
    nb = max(2, min(int(num_buffers or base.num_buffers), 4))
    return KernelConfig(block_q=bq, block_kv=bkv, num_buffers=nb)


def set_config(page_size: int, head_dim: int, cfg: KernelConfig,
               arch: Optional[str] = None) -> None:
    _CACHE[(int(page_size), int(head_dim), arch or _arch())] = cfg


def load_table(path: str) -> int:
    """Merge a persisted tune table into the in-process cache; returns
    the number of entries loaded."""
    if not path or not os.path.exists(path):
        return 0
    with open(path) as f:
        data = json.load(f)
    n = 0
    for row in data.get("entries", []):
        _CACHE[(int(row["page_size"]), int(row["head_dim"]),
                str(row["arch"]))] = KernelConfig(
            block_q=int(row["block_q"]), block_kv=int(row["block_kv"]),
            num_buffers=int(row["num_buffers"]))
        n += 1
    return n


def save_table(path: str) -> None:
    entries = [
        {"page_size": ps, "head_dim": d, "arch": arch, **asdict(cfg)}
        for (ps, d, arch), cfg in sorted(_CACHE.items())
    ]
    with open(path, "w") as f:
        json.dump({"entries": entries}, f, indent=2, sort_keys=True)
        f.write("\n")


def _bench_case(page_size: int, head_dim: int, *, n_kv_heads: int = 2,
                group: int = 2, seqs: int = 3, pages_per_seq: int = 6,
                null_every: int = 3, seed: int = 0):
    """Synthetic ragged workload: a few sequences, sparse tables (every
    ``null_every``-th slot nulled) so the skip path is exercised."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    ps, D = page_size, head_dim
    W = pages_per_seq
    n_pages = 1 + seqs * W
    kv = jnp.asarray(
        rng.standard_normal((n_pages, ps, 2 * n_kv_heads, D)),
        jnp.float32).at[0].set(0.0)
    tbl = np.zeros((seqs, W), np.int32)
    kvl = np.zeros((seqs,), np.int32)
    qls = []
    for s in range(seqs):
        used = W - s % 2                       # ragged page counts
        for j in range(used):
            if null_every and (j + 1) % null_every == 0:
                continue                        # sparse: leave slot null
            tbl[s, j] = 1 + s * W + j
        kvl[s] = used * ps
        qls.append(1 + (s * 5) % (2 * ps))      # ragged query lengths
    cu = np.concatenate([[0], np.cumsum(qls)]).astype(np.int32)
    T = int(cu[-1])
    q = jnp.asarray(
        rng.standard_normal((T, n_kv_heads * group, D)), jnp.float32)
    return q, kv, jnp.asarray(tbl), jnp.asarray(cu), jnp.asarray(kvl), \
        int(max(qls))


def autotune(page_size: int, head_dim: int, *, repeats: int = 3,
             cache_path: Optional[str] = None,
             candidates=None, verbose: bool = False) -> KernelConfig:
    """Time the candidate grid on a synthetic case, cache and return the
    winner. Runs in interpret mode off-TPU (timings then rank the Python
    pipeline, which is still monotone in gather count, and the cache key
    carries arch='cpu' so TPU runs retune)."""
    import jax

    from repro.kernels import resolve_interpret

    from .kernel import ragged_paged_attention_pallas

    q, kv, tbl, cu, kvl, max_q = _bench_case(page_size, head_dim)
    interpret = resolve_interpret()
    D = q.shape[-1]
    best: Tuple[float, KernelConfig] = (float("inf"), best_config(
        page_size, head_dim))
    cand = candidates or [
        KernelConfig(bq, bkv, nb)
        for bq in CANDIDATE_BLOCK_Q for bkv in CANDIDATE_BLOCK_KV
        for nb in CANDIDATE_BUFFERS
    ]
    for cfg in cand:
        eff = resolve_config(page_size, D, max_q, cfg.block_q,
                             cfg.block_kv, cfg.num_buffers)
        pad = -(-max_q // eff.block_q) * eff.block_q
        qp = jax.numpy.pad(q, ((0, pad), (0, 0), (0, 0)))

        def run():
            return ragged_paged_attention_pallas(
                qp, kv, tbl, cu, kvl, scale=1.0 / D ** 0.5,
                max_q_len=max_q, block_q=eff.block_q,
                block_kv=eff.block_kv, num_buffers=eff.num_buffers,
                interpret=interpret).block_until_ready()

        run()                                   # compile / warm
        t0 = time.perf_counter()
        for _ in range(repeats):
            run()
        dt = (time.perf_counter() - t0) / repeats
        if verbose:
            print(f"  tune ps={page_size} D={D} {eff}: {dt * 1e3:.2f} ms")
        if dt < best[0]:
            best = (dt, eff)
    set_config(page_size, D, best[1])
    if cache_path:
        save_table(cache_path)
    return best[1]
