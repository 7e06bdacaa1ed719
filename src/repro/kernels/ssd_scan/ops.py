"""Public jit'd wrapper for the SSD chunk-scan kernel.

Takes the model-layer layout (B, S, H, P) + per-head dt/A and grouped B/C,
folds (batch, head) into the kernel's BH axis."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan_bh


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b, c, *, chunk: int = 256,
             interpret: Optional[bool] = None):
    """x: (B, S, H, P); dt: (B, S, H) post-softplus; a: (H,) negative;
    b/c: (B, S, G, N), H % G == 0. Returns y (B, S, H, P)."""
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    xdt = (x * dt[..., None]).transpose(0, 2, 1, 3).reshape(B * H, S, P)
    da = (dt * a[None, None, :]).transpose(0, 2, 1).reshape(B * H, S)
    bh = jnp.repeat(b, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    ch = jnp.repeat(c, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    y = ssd_scan_bh(xdt.astype(jnp.float32), da.astype(jnp.float32),
                    bh.astype(jnp.float32), ch.astype(jnp.float32),
                    chunk=chunk, interpret=interpret)
    return y.reshape(B, H, S, P).transpose(0, 2, 1, 3).astype(x.dtype)
