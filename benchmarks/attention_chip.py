"""Time the served paged attention alone on the chip, per layer call.

The ragged Pallas kernel (``kernels/paged_attention``) against the jnp
rows form (``paged_attention_rows``) on the same inputs, at the geometry
of the two benchmark cells: a batched decode round with idle rows over a
power-of-2 table, and one question extend resuming mid-document. Both
read a pool stacked over layers, in place, as the served step does, and
each timed program attends every layer of the stack in a loop over the
layer index; the time is divided by the number of layers.

    python -m benchmarks.attention_chip [--repeats 20]

Prints one JSON line per (case, path, launch config): ``ms_per_layer``,
``max_abs_diff`` of the kernel against the rows form on the rows that
carry a sequence, and ``compile_s``. Needs a TPU: on the CPU the kernel
would run in the Pallas interpreter, whose times mean nothing.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import (paged_attention_rows,
                                           ragged_paged_attention)
from repro.kernels.paged_attention.kernel import pages_gathered

PS = 32

# (name, layers, pool pages, Hq, Hkv, table width, kv lengths of the
#  live rows, idle rows, query rows per live row)
CASES = [
    ("docqa.decode", 8, 737, 32, 32, 128,
     [1100, 1500, 1800, 2300, 2700, 3200], 10, 1),
    ("sysprompt.decode", 16, 961, 32, 8, 64,
     [1000, 1050, 1100, 1150, 1200, 1250, 1300, 1350, 1400, 1450, 1500,
      1600], 20, 1),
    ("docqa.extend", 8, 737, 32, 32, 64, [1536 + 96], 0, 96),
    ("sysprompt.extend", 16, 961, 32, 8, 32, [912 + 96], 0, 96),
]
DECODE_CONFIGS = [(1, 4, 2), (1, 16, 2)]
EXTEND_CONFIGS = [(32, 16, 2), (96, 16, 2)]


def _inputs(layers, pages, Hq, Hkv, W, kv_live, idle, q_rows, seed=0):
    D = 128
    key = jax.random.key(seed)
    pool = jax.jit(lambda k: jax.random.normal(
        k, (layers, pages, PS, 2 * Hkv, D), jnp.bfloat16))(key)
    rng = np.random.default_rng(seed)
    rows = len(kv_live) + idle
    tbl = np.zeros((rows, W), np.int32)
    kv_lens = np.zeros((rows,), np.int32)
    free = rng.permutation(np.arange(1, pages))
    used = 0
    for r, kl in enumerate(kv_live):
        n = -(-kl // PS)
        tbl[r, :n] = free[used:used + n]
        used += n
        kv_lens[r] = kl
    T = rows * q_rows
    q = jax.random.normal(jax.random.key(seed + 1), (T, Hq, D),
                          jnp.bfloat16)
    cu = np.arange(rows + 1, dtype=np.int32) * q_rows
    # rows form: one table row and one position per query row
    q_pos = np.repeat(kv_lens, q_rows) - q_rows + np.tile(
        np.arange(q_rows), rows)
    return dict(pool=pool, q=q, tbl=tbl, kv_lens=kv_lens, cu=cu,
                q_pos=np.maximum(q_pos, 0).astype(np.int32),
                rows_tbl=np.repeat(tbl, q_rows, axis=0),
                live=np.repeat(kv_lens > 0, q_rows))


def _time(fn, args, repeats):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / repeats, compile_s


def _every_layer(attend, layers):
    """One program that attends each layer of the stack in turn."""
    def run(q, pool, *rest):
        def body(l, acc):
            return acc + attend(q, pool, *rest, l).astype(jnp.float32)
        return jax.lax.fori_loop(0, layers, body,
                                 jnp.zeros(q.shape, jnp.float32))
    return jax.jit(run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=20)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("attention_chip: needs a TPU", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    for name, L, P, Hq, Hkv, W, kv_live, idle, qr in CASES:
        x = _inputs(L, P, Hq, Hkv, W, kv_live, idle, qr)
        scale = 128 ** -0.5
        rows = _every_layer(
            lambda q, pool, tbl, qpos, l: paged_attention_rows(
                q, pool, tbl, qpos, scale=scale, layer=l), L)
        ref, dt, cs = _time(rows, (x["q"], x["pool"],
                                   jnp.asarray(x["rows_tbl"]),
                                   jnp.asarray(x["q_pos"])), args.repeats)
        ref = np.asarray(ref)[x["live"]]
        base = dict(case=name, device=dev.device_kind, layers=L,
                    live_rows=len(kv_live), idle_rows=idle, table_width=W)
        print(json.dumps(dict(base, path="rows", ms_per_layer=dt / L * 1e3,
                              compile_s=cs)), flush=True)
        configs = DECODE_CONFIGS if qr == 1 else EXTEND_CONFIGS
        for bq, bkv, nb in configs:
            kern = _every_layer(
                lambda q, pool, tbl, cu, kl, l, bq=bq, bkv=bkv, nb=nb:
                ragged_paged_attention(
                    q, pool, tbl, cu, kl, scale=scale, max_q_len=qr,
                    block_q=bq, block_kv=bkv, num_buffers=nb, layer=l), L)
            try:
                out, dt, cs = _time(kern, (
                    x["q"], x["pool"], jnp.asarray(x["tbl"]),
                    jnp.asarray(x["cu"]), jnp.asarray(x["kv_lens"])),
                    args.repeats)
            except Exception as e:  # noqa: BLE001 - report and go on
                print(json.dumps(dict(base, path="kernel", block_q=bq,
                                      block_kv=bkv, num_buffers=nb,
                                      error=str(e)[:400])), flush=True)
                continue
            diff = float(np.abs(np.asarray(out)[x["live"]] - ref).max())
            pages = pages_gathered(x["tbl"], x["cu"], x["kv_lens"],
                                   page_size=PS, max_q_len=qr, block_q=bq)
            print(json.dumps(dict(
                base, path="kernel", block_q=bq, block_kv=bkv,
                num_buffers=nb, ms_per_layer=dt / L * 1e3, compile_s=cs,
                max_abs_diff=diff, pages_per_layer=pages)), flush=True)
        del x
    return 0


if __name__ == "__main__":
    sys.exit(main())
