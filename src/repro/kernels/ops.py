"""jit'd public wrappers around the Pallas kernels.

``dmo_dwconv2d`` is the end-to-end DMO path: it computes the analytic safe
overlap ``O_s`` with the *paper's* formulas (repro.core.overlap.analytic),
converts it to a row-granular arena offset, lays the input into the shared
arena and runs the in-place kernel. It also reports the arena footprint vs
the two-buffer baseline so tests can assert the memory saving.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.graph import Graph
from repro.core.overlap import safe_overlap
from repro.kernels.dmo_arena_dwconv import dmo_dwconv2d_arena
from repro.kernels.inplace_rmsnorm import rmsnorm_scale_residual_inplace
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.runtime import resolve_interpret


def dwconv_overlap_rows(ih: int, iw: int, c: int, k: int, stride: int,
                        pad: int) -> Tuple[int, int, int]:
    """(d_rows, oh, ow): arena row offset of the input derived from the
    paper's analytic O_s, rounded up to whole output rows (block-granular)."""
    oh = (ih + 2 * pad - k) // stride + 1
    ow = (iw + 2 * pad - k) // stride + 1
    g = Graph("k")
    x = g.tensor("x", (ih, iw, c), 4, "input")
    g.op("depthwise_conv2d", [x], (oh, ow, c),
         dict(kernel=(k, k), stride=(stride, stride),
              padding="same" if pad else "valid", multiplier=1))
    os_bytes = safe_overlap(g.ops[0], 0, method="analytic")
    ob = oh * ow * c * 4
    row_bytes = max(iw, ow) * c * 4
    d_rows = math.ceil((ob - os_bytes) / row_bytes)
    return d_rows, oh, ow


@functools.partial(jax.jit, static_argnames=("stride", "pad", "interpret"))
def _dmo_dwconv2d_jit(x: jax.Array, w: jax.Array, stride: int, pad: int,
                      interpret: bool) -> jax.Array:
    ih, iw, c = x.shape
    k = w.shape[0]
    d_rows, oh, ow = dwconv_overlap_rows(ih, iw, c, k, stride, pad)
    rowlen = max(iw, ow) * c
    rows = max(d_rows + ih, oh)
    arena = jnp.zeros((rows, rowlen), jnp.float32)
    arena = arena.at[d_rows:d_rows + ih, : iw * c].set(x.reshape(ih, iw * c))
    arena = dmo_dwconv2d_arena(arena, w.astype(jnp.float32), ih=ih, iw=iw,
                               c=c, stride=stride, pad=pad, d_rows=d_rows,
                               oh=oh, ow=ow, interpret=interpret)
    return arena[:oh, : ow * c].reshape(oh, ow, c)


def dmo_dwconv2d(x: jax.Array, w: jax.Array, stride: int = 1, pad: int = 0,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Depthwise conv through the shared VMEM arena. x: (IH,IW,C) f32.

    The platform's interpret default is resolved *before* the jit
    boundary: the concrete bool is the static cache key."""
    return _dmo_dwconv2d_jit(x, w, stride=stride, pad=pad,
                             interpret=resolve_interpret(interpret))


def dmo_dwconv2d_footprint(ih: int, iw: int, c: int, k: int, stride: int,
                           pad: int) -> Tuple[int, int]:
    """(arena bytes, two-buffer bytes) — the kernel-level memory saving."""
    d_rows, oh, ow = dwconv_overlap_rows(ih, iw, c, k, stride, pad)
    rowlen = max(iw, ow) * c * 4
    return (max(d_rows + ih, oh) * rowlen, ih * iw * c * 4 + oh * ow * c * 4)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rmsnorm_residual_jit(x, g, r, interpret: bool) -> jax.Array:
    return rmsnorm_scale_residual_inplace(x, g, r, interpret=interpret)


def rmsnorm_residual(x: jax.Array, g: jax.Array, r: jax.Array,
                     interpret: Optional[bool] = None) -> jax.Array:
    """In-place fused residual + RMSNorm: out aliases x (O_s = |out|).
    The interpret default resolves before the jit boundary (see
    :func:`dmo_dwconv2d`)."""
    return _rmsnorm_residual_jit(x, g, r,
                                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def _flash_attention_jit(q, k, v, causal: bool, block_q: int, block_k: int,
                         interpret: bool) -> jax.Array:
    return flash_attention_kernel(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Blockwise online-softmax attention. q,k,v: (S,H,D)/(T,H,D). The
    interpret default resolves before the jit boundary (see
    :func:`dmo_dwconv2d`)."""
    return _flash_attention_jit(q, k, v, causal=causal, block_q=block_q,
                                block_k=block_k,
                                interpret=resolve_interpret(interpret))
