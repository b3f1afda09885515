"""Blockwise online-softmax (flash) attention Pallas kernel.

Grid: (heads, q blocks); each step owns one (block_q, D) query tile in VMEM
and loops over (block_k, D) KV tiles with the running (m, l, acc) online
softmax — the score matrix never materialises. MXU-aligned tiles
(block sizes multiples of 128 at the model head dims).

This is the serving hot-spot kernel; the pure-JAX `_sdpa_blockwise` in
repro.models.layers is the same algorithm at the jaxpr level (used for the
CPU dry-run lowering), and `ref.attention` is the exact oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, t: int, block_k: int,
            causal: bool, offset: int):
    bq, d = q_ref.shape[-2:]
    q = q_ref[...].reshape(bq, d).astype(jnp.float32) / (d ** 0.5)
    qi = pl.program_id(1)
    m = jnp.full((bq,), NEG, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)
    nb = t // block_k

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                       # (bq, bk)
        if causal:
            qpos = offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[:, None] + p @ v
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, nb, body, (m, l, acc))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, block_q: int = 128,
                           block_k: int = 128,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q: (S,H,D); k,v: (T,H,D) -> (S,H,D). ``interpret=None`` follows the
    platform (interpret on the CPU, compiled on a TPU)."""
    interpret = resolve_interpret(interpret)
    s, h, d = q.shape
    t = k.shape[0]
    bq = min(block_q, s)
    while s % bq:
        bq -= 1
    bk = min(block_k, t)
    while t % bk:
        bk -= 1
    qh = jnp.moveaxis(q, 1, 0)  # (H,S,D)
    kh = jnp.moveaxis(k, 1, 0)
    vh = jnp.moveaxis(v, 1, 0)
    fn = pl.pallas_call(
        functools.partial(_kernel, t=t, block_k=bk, causal=causal,
                          offset=t - s),
        out_shape=jax.ShapeDtypeStruct((h, s, d), q.dtype),
        grid=(h, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda hi, qi: (hi, qi, 0)),
            pl.BlockSpec((1, t, d), lambda hi, qi: (hi, 0, 0)),
            pl.BlockSpec((1, t, d), lambda hi, qi: (hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda hi, qi: (hi, qi, 0)),
        interpret=interpret,
    )
    out = fn(qh, kh, vh)
    return jnp.moveaxis(out, 0, 1)
