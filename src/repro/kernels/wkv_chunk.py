"""Fused chunked-WKV (RWKV6) Pallas kernel — HC1's "next lever".

The jaxpr-level chunked form (repro.models.ssm._wkv_chunked) already removed
the per-token HBM round-trip, but its per-chunk (Q,Q,H,D) decay tensor and
(Q,Q) attention-like intermediates still live in HBM between einsums. This
kernel fuses the whole time dimension of one (batch, head) pair into a
single program: the recurrent state, the chunk tiles and every pairwise
intermediate stay in VMEM; HBM traffic is exactly one read of r/k/v/log-w
and one write of y — the roofline floor for this op.

Grid: (B, H) — programs are independent (state is per-head), so the grid
axes are genuinely parallel (no diagonal hazard here: each program owns its
output rows exclusively; contrast with the DMO arena kernel where grid
order IS the safety argument).

Validated in interpret mode against both the sequential scan and the
chunked jaxpr implementation (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, sT_ref, *,
            s: int, d: int, q: int):
    """refs: (1, S, D) per (b,h) program; u (1, D); y (1, S, D);
    sT (1, D, D) final state."""
    nc = s // q
    u = u_ref[0]                                           # (D,)
    tq = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    mask_lt = tq > jq                                      # j < t
    eye = (tq == jq).astype(jnp.float32)

    def chunk(ci, state):
        r = r_ref[0, pl.dslice(ci * q, q), :].astype(jnp.float32)   # (Q,D)
        k = k_ref[0, pl.dslice(ci * q, q), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(ci * q, q), :].astype(jnp.float32)
        lw = lw_ref[0, pl.dslice(ci * q, q), :].astype(jnp.float32)
        lwc = jnp.cumsum(lw, axis=0)                       # (Q,D) within chunk
        lwp = jnp.concatenate([jnp.zeros((1, d), jnp.float32),
                               lwc[:-1]], axis=0)
        # pairwise decay exp(lwp[t] - lwc[j]) for j < t, else 0
        lr = lwp[:, None, :] - lwc[None, :, :]             # (Q,Q,D)
        dec = jnp.where(mask_lt[..., None], jnp.exp(lr), 0.0)
        att = jnp.einsum("tjd,td,jd->tj", dec, r, k)
        att = att + eye * jnp.einsum("td,d,td->t", r, u, k)[:, None]
        y = att @ v                                        # (Q,D)
        y = y + (r * jnp.exp(lwp)) @ state                 # cross-chunk
        y_ref[0, pl.dslice(ci * q, q), :] = y.astype(y_ref.dtype)
        k_dec = k * jnp.exp(lwc[-1:] - lwc)
        state = jnp.exp(lwc[-1])[:, None] * state + k_dec.T @ v
        return state

    state = jax.lax.fori_loop(0, nc, chunk,
                              jnp.zeros((d, d), jnp.float32))
    sT_ref[0] = state


def wkv_chunk_kernel(r: jax.Array, k: jax.Array, v: jax.Array,
                     logw: jax.Array, u: jax.Array, q: int = 64,
                     interpret: Optional[bool] = None):
    """r,k,v,logw: (B,S,H,D) (logw = log decay, <= 0); u: (H,D).
    Returns (y (B,S,H,D) f32, final state (B,H,D,D) f32).
    ``interpret=None`` follows the platform (interpret on the CPU,
    compiled on a TPU)."""
    interpret = resolve_interpret(interpret)
    b, s, h, d = r.shape
    assert s % q == 0
    tr = lambda t: jnp.moveaxis(t, 2, 1).reshape(b * h, s, d)
    rr, kk, vv, ll = tr(r), tr(k), tr(v), tr(logw)
    uu = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, d)
    fn = pl.pallas_call(
        functools.partial(_kernel, s=s, d=d, q=q),
        out_shape=(jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, d, d), jnp.float32)),
        grid=(b * h,),
        in_specs=[pl.BlockSpec((1, s, d), lambda i: (i, 0, 0))] * 4
        + [pl.BlockSpec((1, d), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((1, s, d), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, d, d), lambda i: (i, 0, 0))),
        interpret=interpret,
    )
    y, st = fn(rr.astype(jnp.float32), kk.astype(jnp.float32),
               vv.astype(jnp.float32), ll.astype(jnp.float32), uu)
    y = jnp.moveaxis(y.reshape(b, h, s, d), 1, 2)
    return y, st.reshape(b, h, d, d)
