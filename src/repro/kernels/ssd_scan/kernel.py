"""Mamba2 SSD chunk-scan Pallas TPU kernel.

Grid walks (batch*head, chunk) with the chunk axis sequential; the carried
SSM state (headdim x state) lives in VMEM scratch across chunk iterations.
Each step computes the intra-chunk quadratic term with the cumulative decay
mask built in-register from the dt block, adds the carried-state
contribution, and updates the state — the SSD algorithm's chunk recurrence
with one HBM read per operand block (sequential, predictable: the same IO
shape the paper's MRM targets).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _ssd_kernel(xdt_ref, da_ref, b_ref, c_ref, y_ref, state_ref, *,
                n_chunks: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = xdt_ref[0].astype(jnp.float32)  # (L, P) x*dt
    da = da_ref[0].astype(jnp.float32)    # (L,)   dt*A (log-decay)
    b = b_ref[0].astype(jnp.float32)      # (L, N)
    c = c_ref[0].astype(jnp.float32)      # (L, N)

    cs = jnp.cumsum(da)                        # (L,)
    seg = cs[:, None] - cs[None, :]            # seg(l, s) = sum_{s+1..l}
    L = da.shape[0]
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    decay = jnp.where(tri, jnp.exp(seg), 0.0)  # (L, L)

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    y_in = jax.lax.dot_general(cb * decay, xdt, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (L, P)

    state = state_ref[...]  # (P, N)
    y_off = jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (L, P)
    y_off = y_off * jnp.exp(cs)[:, None]

    tail = jnp.exp(cs[-1] - cs)  # (L,)
    new_state = jax.lax.dot_general(xdt * tail[:, None], b,
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = state * jnp.exp(cs[-1]) + new_state
    y_ref[0] = (y_in + y_off).astype(y_ref.dtype)


def ssd_scan_bh(xdt, da, b, c, *, chunk: int = 256,
                interpret: Optional[bool] = None):
    """xdt: (BH, S, P) (x pre-multiplied by dt); da: (BH, S) log-decays;
    b/c: (BH, S, N). Returns y (BH, S, P). S must be divisible by chunk."""
    BH, S, P = xdt.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    n_chunks = S // chunk

    kernel = functools.partial(_ssd_kernel, n_chunks=n_chunks, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b_, ci: (b_, ci, 0)),
            pl.BlockSpec((1, chunk), lambda b_, ci: (b_, ci)),
            pl.BlockSpec((1, chunk, N), lambda b_, ci: (b_, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b_, ci: (b_, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b_, ci: (b_, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xdt, da, b, c)
