import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell
against the production mesh with ShapeDtypeStruct inputs (no allocation),
then extract memory analysis, cost analysis, and trip-count-aware roofline
terms (launch/hlo_analysis.py).

A CPU-only tool. The XLA_FLAGS line above MUST precede any jax import
(jax locks the device count at first init), and the orchestrator (--all)
runs each cell in its own subprocess. Nothing on the chip path
(``launch/serve.py``, ``chip_smoke.py``) may import this module: it would
replace the chip's devices with 512 host ones, and its children could
not reach a chip the parent holds.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both]
  python -m repro.launch.dryrun --arch ... --set mla_absorb=True --variant absorb
"""
__doc__ = _DOC

import argparse
import dataclasses
import json
import pathlib
import subprocess
import sys
import time

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _parse_set(kvs):
    out = {}
    for kv in kvs or []:
        k, v = kv.split("=", 1)
        try:
            out[k] = json.loads(v)
        except json.JSONDecodeError:
            out[k] = v
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, variant: str = "base",
             overrides=None, rules_name: str = "default", zero1: bool = False,
             fsdp: bool = False, verbose: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_shape
    from repro.launch import mesh as meshmod
    from repro.launch.hlo_analysis import analyze, roofline_terms
    from repro.models import transformer as tfm
    from repro.models.frontends import decode_input_specs, input_specs
    from repro.models import param as prm
    from repro.optim import OptConfig, opt_state_defs
    from repro.runtime import sharding as shd
    from repro.train import make_train_step

    t0 = time.time()
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    mesh = meshmod.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    rules = shd.SEQUENCE_PARALLEL_RULES if rules_name == "sp" else shd.DEFAULT_RULES
    sh = shd.ShardCtx(mesh, rules)

    defs = tfm.model_defs(cfg)
    pspecs = shd.param_partition_specs(defs, mesh, rules)
    if fsdp:
        pspecs = _zero1(pspecs, defs, mesh)  # 2D (model x data) weight sharding
    p_shardings = jax.tree.map(
        lambda sp: jax.sharding.NamedSharding(mesh, sp), pspecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    params_abs = prm.abstract(defs, cfg.param_dtype, p_shardings)

    B, S = shape.global_batch, shape.seq_len

    def batch_abs_of(specs: dict) -> dict:
        out = {}
        for k, sds in specs.items():
            axes = ("act_batch",) + (None,) * (len(sds.shape) - 1)
            out[k] = jax.ShapeDtypeStruct(
                sds.shape, sds.dtype,
                sharding=shd.sharding_for(axes, sds.shape, mesh, rules))
        return out

    if shape.kind == "train":
        oc = OptConfig()
        odefs = opt_state_defs(defs)
        orules = dict(rules)
        ospecs = shd.param_partition_specs(odefs, mesh, orules)
        if zero1 or fsdp:
            ospecs = _zero1(ospecs, odefs, mesh)
        oshard = jax.tree.map(
            lambda sp: jax.sharding.NamedSharding(mesh, sp), ospecs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        opt_abs = prm.abstract(odefs, "float32", oshard)
        batch_abs = batch_abs_of(input_specs(cfg, shape))
        step = make_train_step(cfg, oc, sh)
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(params_abs, opt_abs, batch_abs)
    elif shape.kind == "prefill":
        batch_abs = batch_abs_of(input_specs(cfg, shape))

        def step(params, batch):
            return tfm.prefill(cfg, params, batch, sh)

        lowered = jax.jit(step).lower(params_abs, batch_abs)
    else:  # decode
        caches_abs = _abstract_caches(cfg, sh, mesh, rules, B, S)
        dspecs = decode_input_specs(cfg, shape)
        tok = dspecs["last_tokens"]
        tok_axes = ("act_batch",) + (None,) * (len(tok.shape) - 1)
        tok_abs = jax.ShapeDtypeStruct(
            tok.shape, tok.dtype,
            sharding=shd.sharding_for(tok_axes, tok.shape, mesh, rules))
        pos_abs = jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))

        def step(params, caches, last_tokens, cur_pos):
            return tfm.decode(cfg, params, caches, last_tokens, cur_pos, sh)

        lowered = jax.jit(step, donate_argnums=(1,)).lower(
            params_abs, caches_abs, tok_abs, pos_abs)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    ana = analyze(text, num_devices=n_dev)
    rt = roofline_terms(ana, peak_flops=meshmod.PEAK_FLOPS_BF16,
                        hbm_bw=meshmod.HBM_BW, ici_bw=meshmod.ICI_BW)

    counts = cfg.param_counts()
    tokens = B * S if shape.kind in ("train", "prefill") else B
    model_flops = (6 if shape.kind == "train" else 2) * counts["active"] * tokens
    hlo_flops_global = ana["flops"] * n_dev
    mem_gib = (ma.argument_size_in_bytes + ma.output_size_in_bytes +
               ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "variant": variant,
        "n_devices": n_dev, "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_gib": ma.argument_size_in_bytes / 2**30,
            "output_gib": ma.output_size_in_bytes / 2**30,
            "temp_gib": ma.temp_size_in_bytes / 2**30,
            "alias_gib": ma.alias_size_in_bytes / 2**30,
            "per_device_gib": mem_gib,
            "fits_16gib": bool(mem_gib <= 16.0),
        },
        "xla_cost_analysis": {k: cost.get(k) for k in
                              ("flops", "bytes accessed", "transcendentals")},
        "hlo_analysis": {k: ana[k] for k in
                         ("flops", "dot_flops", "elementwise_flops",
                          "transcendentals", "bytes_accessed",
                          "collective_operand_bytes", "collective_wire_bytes")},
        "collectives": ana["collectives"],
        "roofline": rt,
        "model_flops": {
            "params_total": counts["total"],
            "params_active": counts["active"],
            "tokens_per_step": tokens,
            "model_flops": model_flops,
            "hlo_flops_global": hlo_flops_global,
            "useful_ratio": model_flops / hlo_flops_global if hlo_flops_global else None,
        },
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind} ({variant}): "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s "
              f"mem/dev {mem_gib:.2f} GiB "
              f"terms c/m/x = {rt['compute_s']:.2e}/{rt['memory_s']:.2e}/"
              f"{rt['collective_s']:.2e}s dom={rt['dominant']}")
        print("memory_analysis:", ma)
        print("cost_analysis (raw, per-device, loop bodies counted once):",
              {k: cost.get(k) for k in ("flops", "bytes accessed")})
    return result


def _zero1(ospecs, odefs, mesh):
    """Extend optimizer-state specs: shard the first unsharded divisible dim
    over the data axis (ZeRO-1)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.models.param import is_def

    dsz = mesh.shape.get("data", 1)

    def extend(spec, d):
        if not hasattr(d, "shape") or not d.shape:
            return spec
        parts = list(spec) + [None] * (len(d.shape) - len(spec))
        for i, dim in enumerate(d.shape):
            if parts[i] is None and dim % dsz == 0 and dsz > 1:
                parts[i] = "data"
                return P(*parts)
        return spec

    return jax.tree.map(extend, ospecs, odefs,
                        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


def _abstract_caches(cfg, sh, mesh, rules, batch: int, max_len: int):
    """Abstract decode caches with shardings attached."""
    import jax
    from repro.models import transformer as tfm
    from repro.runtime import sharding as shd

    caches = tfm.init_caches(cfg, batch, max_len, abstract=True)
    kvx = sh.kv_axes(cfg)

    def axes_for(path_keys, arr):
        nd = len(arr.shape)
        # leading dim is the scanned-layers stack
        name = path_keys[-1]
        if name in ("k", "v"):
            return ("layers",) + kvx
        if name == "pos":
            return ("layers",) + kvx[:2]
        if name == "c":  # MLA latents: shard the cache sequence over model
            return ("layers", "act_batch", "act_kv_seq", None)
        if name == "kr":
            return ("layers", "act_batch", "act_kv_seq", None)
        if name == "conv":
            return ("layers", "act_batch", None, "ssm_inner")
        if name == "state":
            return ("layers", "act_batch", "act_heads", None, None)
        return ("layers",) + (None,) * (nd - 1)

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, tuple):
            return tuple(walk(v, path) for v in tree)
        axes = axes_for(path, tree)
        shardng = shd.sharding_for(axes[: len(tree.shape)], tree.shape, mesh, rules)
        return jax.ShapeDtypeStruct(tree.shape, tree.dtype, sharding=shardng)

    return walk(caches)


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------


def all_cells(mesh_kinds):
    from repro.configs import ASSIGNED_ARCHS, cells

    for mesh_kind in mesh_kinds:
        for arch, shape_name in cells(ASSIGNED_ARCHS):
            yield arch, shape_name, mesh_kind


def orchestrate(mesh_kinds, skip_existing=True, timeout=7200, archs=None, shapes=None):
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    results = []
    todo = [c for c in all_cells(mesh_kinds)
            if (archs is None or c[0] in archs) and (shapes is None or c[1] in shapes)]
    for i, (arch, shape_name, mesh_kind) in enumerate(todo):
        out = ARTIFACTS / f"{arch}__{shape_name}__{mesh_kind}__base.json"
        if skip_existing and out.exists():
            print(f"[{i+1}/{len(todo)}] skip (exists): {out.name}")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape_name, "--mesh", mesh_kind, "--out", str(out)]
        print(f"[{i+1}/{len(todo)}] {' '.join(cmd[2:])}", flush=True)
        t0 = time.time()
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout,
                           env=dict(os.environ, PYTHONPATH="src"))
        dt = time.time() - t0
        if r.returncode != 0:
            fail = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                    "ok": False, "error": r.stderr[-4000:], "wall_s": dt}
            out.write_text(json.dumps(fail, indent=1))
            print(f"  FAILED after {dt:.0f}s; tail:\n{r.stderr[-1500:]}", flush=True)
        else:
            print(f"  ok in {dt:.0f}s", flush=True)
        results.append(out)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="base")
    ap.add_argument("--set", nargs="*", help="config overrides key=value")
    ap.add_argument("--rules", default="default", choices=["default", "sp"])
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--fsdp", action="store_true",
                    help="2D (model x data) weight sharding (ZeRO-3-style)")
    ap.add_argument("--out")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", nargs="*")
    ap.add_argument("--shapes", nargs="*")
    ap.add_argument("--no-skip", action="store_true")
    args = ap.parse_args()

    if args.all:
        kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        orchestrate(kinds, skip_existing=not args.no_skip,
                    archs=args.archs, shapes=args.shapes)
        return

    res = run_cell(args.arch, args.shape, args.mesh, variant=args.variant,
                   overrides=_parse_set(args.set), rules_name=args.rules,
                   zero1=args.zero1, fsdp=args.fsdp)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
