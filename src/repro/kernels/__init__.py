"""Pallas TPU kernels for the workload's compute hot-spots.

The paper's workload analysis (§2.2: decode reads all weights + the whole
KV cache per token, sequentially and predictably) identifies attention as
the IO hot-spot; the Pallas kernels express that insight TPU-natively:
block-granular HBM->VMEM streaming with MXU-aligned tiles.

Each kernel package has:
- ``kernel.py`` — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
- ``ops.py``    — jit'd public wrapper (layout plumbing, defaults)
- ``ref.py``    — pure-jnp oracle used by the allclose test sweeps

Pallas interpret mode is decided in one place, :func:`resolve_interpret`:
on when JAX runs on the CPU (the test suite), off everywhere else, so a
kernel on the chip is always the compiled kernel. The model's dry-run
path uses the pure-XLA implementations (DESIGN.md §4).
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode for a kernel launch: an explicit value wins
    (tests force it), otherwise on only when JAX's platform is ``cpu``."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"
