"""Flash-attention Pallas TPU kernel (prefill / training forward).

Tiling: grid = (BH, nq, nkv) with the kv axis innermost ("arbitrary" —
sequential), so each (batch*kv-head, q-block) streams its KV blocks
HBM->VMEM in order while the online-softmax state (m, l, acc) lives in VMEM
scratch. Q blocks are (q_block, head_dim) MXU-aligned tiles; the causal /
sliding-window mask is computed from program ids, never materialized in HBM.

This is the TPU-native expression of the paper's "IO is sequential and
predictable" observation applied to attention compute.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, cap: Optional[float], window: Optional[int],
                  causal: bool, q_block: int, kv_block: int, nkv: int,
                  kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (q_block, d)
    k = k_ref[0]  # (kv_block, d)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cap is not None:
        s = jnp.tanh(s / cap) * cap

    qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
    kpos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
    mask = kpos < kv_len
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > (qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nkv - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bh(q, k, v, *, scale: float, cap: Optional[float] = None,
                       window: Optional[int] = None, causal: bool = True,
                       q_block: int = 512, kv_block: int = 512,
                       kv_len: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """q: (BH, Sq, D); k/v: (BH, Skv, D) -> (BH, Sq, D).

    BH folds batch x kv-head x group; D should be a multiple of 128 on real
    TPUs (interpret mode accepts any size for the test sweeps).
    """
    BH, Sq, D = q.shape
    Skv = k.shape[1]
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    assert Sq % q_block == 0 and Skv % kv_block == 0
    nq = Sq // q_block
    nkv = Skv // kv_block
    kv_len = Skv if kv_len is None else kv_len

    kernel = functools.partial(
        _flash_kernel, scale=scale, cap=cap, window=window, causal=causal,
        q_block=q_block, kv_block=kv_block, nkv=nkv, kv_len=kv_len)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, q_block, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, kv_block, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, kv_block, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),    # m
            pltpu.VMEM((q_block,), jnp.float32),    # l
            pltpu.VMEM((q_block, D), jnp.float32),  # acc
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
