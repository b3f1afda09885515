"""In-place fused residual-add + RMSNorm Pallas kernel.

The paper's ideal diagonal case (Fig. 3a): elementwise(-per-row) ops have
``O_s = |out|`` — input and output fully share storage. Realised here with
``input_output_aliases={0: 0}``: the residual stream buffer is updated in
place, one (block, d) VMEM tile per grid step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret


def _kernel(x_ref, g_ref, r_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = r + x * jax.lax.rsqrt(ms + eps) * g_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_scale_residual_inplace(x: jax.Array, g: jax.Array, r: jax.Array,
                                   eps: float = 1e-6, block: int = 128,
                                   interpret: Optional[bool] = None
                                   ) -> jax.Array:
    """x, r: (N, d); g: (d,). Output aliases x. ``interpret=None`` follows
    the platform (interpret on the CPU, compiled on a TPU)."""
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    b = min(block, n)
    while n % b:
        b -= 1
    grid = (n // b,)
    fn = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((b, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((b, d), lambda i: (i, 0)),
        input_output_aliases={0: 0},
        interpret=interpret,
    )
    return fn(x, g, r)
