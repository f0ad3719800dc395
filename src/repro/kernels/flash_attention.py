"""Pallas TPU flash attention (block-wise online softmax).

Covers the zoo's attention variants: causal, GQA (q-head groups share a kv
head via BlockSpec index mapping), sliding-window (gemma2 local layers),
logit soft-capping (gemma2), and right-aligned queries (decode/prefill with
sq < skv).

Tiling: grid (B*Hq, Sq/bq, Skv/bk); the kv dimension is innermost, so the
(m, l, acc) accumulators live in VMEM scratch and persist across kv steps —
the canonical sequential-grid accumulation pattern. Default tiles bq=bk=128
keep the working set (q, k, v, acc tiles + logits) well under VMEM while
keeping the MXU contraction dims at 128.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window, softcap, bq: int, bk: int,
            sq: int, skv: int, num_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # [bq, d]
    k = k_ref[0].astype(jnp.float32)  # [bk, d]
    v = v_ref[0].astype(jnp.float32)  # [bk, d]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (skv - sq)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_scr[...]          # [bq, 1]
    l_prev = l_scr[...]          # [bq, 1]
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
    l_cur = alpha * l_prev + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur
    l_scr[...] = l_cur

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k",
    "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = None,
                    softcap: float = None, scale: float = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D]; Hq % Hkv == 0."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0
    nq, nk = sq // bq, skv // bk
    scale = scale if scale is not None else float(1.0 / (d ** 0.5))

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    def kv_index(bh, qi, ki):
        return ((bh // hq) * hkv + (bh % hq) // group, ki, 0)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, sq=sq, skv=skv, num_kv_blocks=nk)

    out = pl.pallas_call(
        kern,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
