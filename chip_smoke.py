#!/usr/bin/env python3
"""On-chip smoke check: the served path runs on a TPU and answers right.

    python chip_smoke.py            # one chip: kernel and serve phases
    python chip_smoke.py --chips 4  # four chips: the per-device fleet only

The model is qwen3-8b at its published widths (d_model 4096, 32 query and
8 KV heads of 128, d_ff 12288, vocab 151,936) cut to its first 16 of 36
layers (``configs.at_depth``), with random bf16 weights drawn from
--seed. It is built and served through the entry points of
``python -m repro.launch.serve``: ``model_config``, ``build_engine``,
``replica_params`` and ``build_frontend``.

Phases, each of which fails the run:

1. device — JAX must find a TPU (no CPU stand-in) and as many chips as
   asked for;
2. kernel — the Pallas ``ragged_paged_attention``, compiled, at qwen3-8b
   decode and 512-row extend geometry against ``ragged_paged_attention_ref``
   in fp32;
3. serve — one ``ServeEngine`` on the paged plane answers prompts that
   share a head; every request finishes, nothing is dropped, the radix
   tree hits, every token is in the vocabulary, and the first-token logits
   of one prompt match the non-paged ``transformer.prefill``;
4. fleet (``--chips 4`` only) — a ``ClusterFrontend`` of 4 replicas with
   prefix migration, each replica's weights and page pool on its own chip,
   decodes exactly what the same fleet decodes with all four replicas on
   chip 0 sharing one weight copy, with the same migration and prefix-hit
   counters.

Lines before the last say what each phase did; the times and byte counts
there are smoke readings, not metrics. The last line is one JSON object
naming the device, printed only when every phase passed. Everything runs
in this one process and no other is started: a chip belongs to one
process at a time.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH, LAYERS = "qwen3-8b", 16
PUBLISHED = dict(d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
                 d_ff=12288, vocab_size=151936)


class SmokeFailure(Exception):
    """A phase found the system not running, or answering wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_phase(chips: int, cache_dir: str):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"JAX found no device: {e}") from e
    d0 = devices[0]
    check(d0.platform == "tpu",
          f"no TPU found: JAX runs on {d0.platform!r} ({d0.device_kind})")
    check(len(devices) >= chips,
          f"{chips} chips asked for, JAX finds {len(devices)}")
    say("device", f"platform={d0.platform} kind={d0.device_kind!r} "
                  f"count={len(devices)} compile_cache={cache_dir}")
    return devices


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def kernel_phase(cfg, page_tokens: int, seed: int) -> None:
    """Compiled kernel vs fp32 reference at the model's attention
    geometry. The limit is 2^-6 of the largest reference output: the
    kernel returns bf16 (rounding alone moves an output by up to 2^-9 of
    its size) and may multiply probabilities by values in bf16 passes on
    the MXU, an error of the same order averaged over the keys. A page
    attended twice, skipped or misplaced moves the output by far more."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import (ragged_paged_attention,
                                               ragged_paged_attention_ref)

    Hq, Hkv, D, ps = (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                      page_tokens)
    rng = np.random.default_rng(seed)
    cases = {
        "decode": ([1] * 8, [int(n) for n in rng.integers(1, 2048, 8)]),
        "extend": ([512, 1, 37], [1024, 700, 101]),
    }
    for name, (q_lens, kv_lens) in cases.items():
        n_pages = [-(-k // ps) for k in kv_lens]
        W = max(n_pages)
        P = 1 + sum(n_pages)
        ids = rng.permutation(np.arange(1, P)).astype(np.int32)
        table = np.zeros((len(q_lens), W), np.int32)
        at = 0
        for s, n in enumerate(n_pages):
            table[s, :n] = ids[at:at + n]
            at += n
        kq, kk = jax.random.split(jax.random.key(seed))
        q = jax.random.normal(kq, (sum(q_lens), Hq, D), jnp.bfloat16)
        kv = jax.random.normal(kk, (P, ps, 2 * Hkv, D), jnp.bfloat16)
        kv = kv.at[0].set(0)
        cu = jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
        kl = jnp.asarray(kv_lens, jnp.int32)
        tbl = jnp.asarray(table)
        t0 = time.perf_counter()
        out = ragged_paged_attention(q, kv, tbl, cu, kl, scale=D ** -0.5,
                                     max_q_len=max(q_lens), backend="pallas",
                                     interpret=False).block_until_ready()
        first_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            ref = ragged_paged_attention_ref(
                q.astype(jnp.float32), kv.astype(jnp.float32), tbl, cu, kl,
                scale=D ** -0.5)
        out, ref = np.asarray(out, np.float32), np.asarray(ref)
        check(out.shape == ref.shape, f"kernel {name}: shape {out.shape}")
        check(bool(np.isfinite(out).all()), f"kernel {name}: non-finite")
        err = float(np.abs(out - ref).max())
        limit = 2.0 ** -6 * float(np.abs(ref).max())
        say("kernel", f"{name}: q_lens={q_lens} kv_lens={kv_lens} "
                      f"max|err|={err:.3e} limit={limit:.3e} "
                      f"first call incl. compile {first_s:.2f}s")
        check(err <= limit, f"kernel {name}: max|err| {err} > {limit}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve_args(extra=()):
    from repro.launch import serve

    return serve.parse_args(["--arch", ARCH, "--layers", str(LAYERS),
                             "--slots", "4", "--page-tokens", "32",
                             "--max-new", "16", *extra])


def prompts(rng, vocab: int, n: int, head: list, tail: int = 32) -> list:
    """``n`` prompts of ``head`` + a distinct ``tail``-token run."""
    return [head + [int(t) for t in rng.integers(2, vocab, tail)]
            for _ in range(n)]


def serve_phase(cfg, full, params, args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.models import transformer as tfm
    from repro.serving.telemetry import recorder

    clock = recorder()      # XLA compiles (or cache loads) in the process

    eng = serve.build_engine(args, cfg, full, params)
    check(eng.paged, "engine is not on the paged compute plane")
    # record the logits the engine samples from (its first-token logits
    # for a prompt, then batched decode logits)
    sampled = []
    sample = eng.backend.sample

    def recording_sample(logits):
        sampled.append(logits)
        return sample(logits)

    eng.backend.sample = recording_sample

    rng = np.random.default_rng(args.seed)
    head = [int(t) for t in rng.integers(2, cfg.vocab_size, 64)]
    probe = prompts(rng, cfg.vocab_size, 1, head)[0]
    rest = (prompts(rng, cfg.vocab_size, 3, head)
            + prompts(rng, cfg.vocab_size, 1, []))
    rest[-1] = rest[-1] + [int(t) for t in rng.integers(2, cfg.vocab_size,
                                                        64)]

    steps = []

    def drain():
        while not eng.sched.idle:
            n0 = clock.jax_compiles
            t0 = time.perf_counter()
            r = eng.step()
            steps.append((time.perf_counter() - t0, r["prefill_tokens"],
                          clock.jax_compiles > n0))

    t_serve = time.perf_counter()
    c0 = clock.jax_compile_s
    rids = [eng.submit(probe, max_new_tokens=args.max_new)]
    drain()                     # the probe registers the shared head
    rids += [eng.submit(p, max_new_tokens=args.max_new) for p in rest]
    drain()
    serve_s = time.perf_counter() - t_serve
    rep = eng.report()

    check(rep["finished"] == len(rids),
          f"{rep['finished']} of {len(rids)} requests finished")
    check(rep["dropped_allocs"] == 0,
          f"dropped_allocs = {rep['dropped_allocs']}")
    check(rep["prefix_hits"] > 0, "no radix prefix hit")
    check(all(1 <= len(eng.outputs[r]) <= args.max_new for r in rids),
          "a request decoded no token or too many")
    toks = [t for r in rids for t in eng.outputs[r]]
    check(all(0 <= t < cfg.vocab_size for t in toks),
          "a decoded token lies outside the vocabulary")

    # the probe's first token came from sampled[0]; the non-paged
    # whole-prompt prefill must give the same logits. The limit is 2% of
    # the reference's norm: both run the bf16 model, but the paged step
    # attends page by page and the prefill in flash-style chunks, so the
    # bf16 roundings of 16 layers differ; a wrong position, mask or page
    # moves the logits by the order of their norm.
    served = np.asarray(sampled[0], np.float32).reshape(-1)
    ref_fn = jax.jit(lambda p, t: tfm.prefill(cfg, p, {"tokens": t})[0])
    ref = np.asarray(ref_fn(params, jnp.asarray([probe], jnp.int32)),
                     np.float32).reshape(-1)
    check(bool(np.isfinite(served).all()), "non-finite served logits")
    rel = float(np.linalg.norm(served - ref) / np.linalg.norm(ref))
    say("serve", f"first-token logits vs transformer.prefill: "
                 f"rel L2 err {rel:.3e} (limit 2.0e-02), "
                 f"max|err| {float(np.abs(served - ref).max()):.3e} "
                 f"of max|ref| {float(np.abs(ref).max()):.3e}")
    check(rel <= 2e-2, f"first-token logits rel err {rel} > 0.02")

    decode = [dt for dt, pre, compiled in steps if pre == 0 and not compiled]
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    stats = jax.devices()[0].memory_stats() or {}
    say("serve", f"{ARCH} d_model={cfg.d_model} heads={cfg.n_heads}/"
                 f"{cfg.n_kv_heads} d_ff={cfg.d_ff} layers={cfg.num_layers}"
                 f"/{full.num_layers}: {rep['finished']} requests, "
                 f"{len(toks)} tokens, prefix_hits={rep['prefix_hits']}, "
                 f"prefill_tokens_skipped={rep['prefill_tokens_skipped']}, "
                 f"dropped_allocs={rep['dropped_allocs']}")
    say("serve", f"smoke readings (not metrics): {len(steps)} engine steps "
                 f"in {serve_s:.2f}s wall, XLA compile "
                 f"{clock.jax_compile_s - c0:.2f}s over the run; steady decode "
                 f"step median "
                 f"{statistics.median(decode) * 1e3 if decode else float('nan'):.2f} ms "
                 f"over {len(decode)} steps; weights {weight_bytes} B; "
                 f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# fleet (four chips)
# ---------------------------------------------------------------------------


def run_fleet(args, cfg, full, replica_params: list, seed: int):
    """Serve the fleet traffic; returns (per-request tokens, counters)."""
    import numpy as np

    from repro.launch import serve

    engines = [serve.build_engine(args, cfg, full, p) for p in replica_params]
    fe = serve.build_frontend(args, engines)
    rng = np.random.default_rng(seed)
    head = [int(t) for t in rng.integers(2, cfg.vocab_size, 64)]
    rids = [fe.submit(prompts(rng, cfg.vocab_size, 1, head)[0],
                      args.max_new, session_key="s0")]
    fe.run_until_idle()         # the first replica now owns the head
    for i, p in enumerate(prompts(rng, cfg.vocab_size, 7, head)):
        rids.append(fe.submit(p, args.max_new, session_key=f"s{i % 3}"))
    rep = fe.run_until_idle()
    counters = {"migrations": rep["interconnect"]["migrations"],
                "prefix_hits": rep["prefix_hits"],
                "prefix_hits_migrated": rep["prefix_hits_migrated"],
                "finished": rep["finished"],
                "dropped_allocs": rep["dropped_allocs"]}
    return [fe.output(r) for r in rids], counters, engines


def fleet_phase(cfg, full, params, args, devices) -> None:
    import jax

    from repro.launch import serve

    n = 4
    t0 = time.perf_counter()
    placed = serve.replica_params(params, n)
    own_tokens, own, engines = run_fleet(args, cfg, full, placed, args.seed)
    for i, e in enumerate(engines):
        where = {d for leaf in jax.tree.leaves(e.backend.paged_caches)
                 for d in leaf.devices()}
        where |= {d for leaf in jax.tree.leaves(e.params)
                  for d in leaf.devices()}
        check(where == {devices[i % len(devices)]},
              f"replica {i} lives on {where}")
    t_own = time.perf_counter() - t0
    del engines, placed
    t0 = time.perf_counter()
    shared_tokens, shared, _ = run_fleet(args, cfg, full, [params] * n,
                                         args.seed)
    t_shared = time.perf_counter() - t0
    say("fleet", f"{n} replicas, one per chip: {own} ({t_own:.2f}s wall)")
    say("fleet", f"{n} replicas on chip 0, one weight copy: {shared} "
                 f"({t_shared:.2f}s wall)")
    check(own["finished"] == len(own_tokens), "per-chip fleet unfinished")
    check(own["migrations"] > 0, "no prefix migration happened")
    check(own == shared, f"counters differ: {own} vs {shared}")
    same = sum(a == b for a, b in zip(own_tokens, shared_tokens))
    say("fleet", f"decoded tokens identical for {same} of "
                 f"{len(own_tokens)} requests")
    check(same == len(own_tokens), "decoded tokens differ between fleets")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the per-device fleet phase")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)
    if not (ROOT / "src" / "repro" / "launch" / "serve.py").is_file():
        print("chip_smoke: FAIL: run from a checkout of the repository "
              "(src/repro is missing next to this script)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import serve

        cache_dir = serve.configure_compile_cache()
        devices = device_phase(opts.chips, cache_dir)

        import jax

        from repro.models import init_params

        fleet = ["--replicas", "4", "--migrate-prefixes"]
        args = serve_args(["--seed", str(opts.seed)]
                          + (fleet if opts.chips == 4 else []))
        cfg, full = serve.model_config(args)
        widths = {k: getattr(cfg, k) for k in PUBLISHED}
        check(widths == PUBLISHED, f"not the published widths: {widths}")
        t0 = time.perf_counter()
        params = init_params(cfg, jax.random.key(opts.seed))
        jax.block_until_ready(params)
        say("device", f"{ARCH} weights initialised in "
                      f"{time.perf_counter() - t0:.2f}s")
        if opts.chips == 4:
            fleet_phase(cfg, full, params, args, devices)
        else:
            kernel_phase(cfg, args.page_tokens, opts.seed)
            serve_phase(cfg, full, params, args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    cache = Path(cache_dir)
    say("device", f"compile cache {cache_dir} holds "
                  f"{len(list(cache.iterdir())) if cache.is_dir() else 0} "
                  f"entries")
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
