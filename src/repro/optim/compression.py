"""Int8 gradient compression with error feedback.

For the data-parallel all-reduce at 1000+-node scale, f32/bf16 ring
all-reduce moves ~2x gradient bytes over the slowest links. The standard
mitigation is quantized reduce-scatter + all-gather with *error feedback*
(the quantization residual is carried to the next step so the compression
bias vanishes in expectation).

`compressed_psum` implements the int8 RS+AG inside shard_map (bytes moved
~= 1/4 of bf16); `ef_compress/ef_decompress` are the host-math primitives
used by tests and by the trainer's error-feedback buffers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int8(x):
    """Per-tensor symmetric int8 quantization. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x)) + 1e-12
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def ef_compress(grad, error):
    """Error-feedback compression: returns (q, scale, new_error)."""
    g = grad.astype(jnp.float32) + error
    q, scale = quantize_int8(g)
    new_error = g - dequantize_int8(q, scale)
    return q, scale, new_error


def compressed_psum(x, axis: str):
    """Quantized reduce-scatter + all-gather mean along `axis`.

    Call inside shard_map with any per-device array shape (flattened and
    padded internally). Bytes on the wire: 2 * |x| int8 (+ scales) instead
    of 2 * |x| f32.
    """
    d = jax.lax.axis_size(axis)
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % d
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    chunks = flat.reshape(d, (n + pad) // d)
    q, scale = quantize_int8(chunks)
    # reduce-scatter: every peer receives my chunk for its index
    recv = jax.lax.all_to_all(q, axis, 0, 0, tiled=False)
    scales = jax.lax.all_gather(scale, axis)          # [d]
    partial = jnp.sum(
        recv.astype(jnp.float32) * scales.reshape(d, 1), axis=0) / d
    q2, s2 = quantize_int8(partial)
    allq = jax.lax.all_gather(q2, axis)                # [d, n/d]
    alls = jax.lax.all_gather(s2, axis)                # [d]
    out = (allq.astype(jnp.float32) * alls.reshape(d, 1)).reshape(-1)
    return out[:n].reshape(x.shape).astype(x.dtype)
