"""Operations and bytes each layer needs, from its shapes alone.

The count is the algorithm's, not the program's: 2 operations per
multiply-accumulate of conv, depthwise and fully connected layers, one per
input element of a mean, none for a softmax; bytes are the layer's input,
output and weights at the tier's width, the weights read once per call.
It stays the same however the program implements an op: fused, one kernel
for the whole graph, or streamed.

A reference module (``bench/reference/<arch>.py``) whose layers include
kinds this file has no formula for gives its own ``layer_work(layer,
dtype_bytes, batch)``, returning ``(operations, bytes)`` or ``None`` for a
kind it leaves to this file (:func:`work_of`).
"""
from __future__ import annotations

from typing import Callable, Iterable, Optional, Tuple

import numpy as np

#: Bytes per element of each tier.
WIDTH = {"int8": 1, "f32": 4}


def layer_work(layer, dtype_bytes: int, batch: int = 1) -> Tuple[int, int]:
    """(operations, bytes) of one layer for one call of ``batch`` images."""
    n_in = int(np.prod(layer.in_shape))
    n_out = int(np.prod(layer.out_shape))
    ws = layer.weight_shape
    n_w = int(np.prod(ws)) if ws is not None else 0
    if layer.kind == "conv2d":
        macs = n_out * ws[0] * ws[1] * ws[2]
    elif layer.kind == "depthwise_conv2d":
        macs = n_out * ws[0] * ws[1]
    elif layer.kind == "fully_connected":
        macs = n_in * n_out
    elif layer.kind in ("mean", "softmax"):
        macs = 0
    else:
        raise ValueError(f"no work formula for layer kind {layer.kind!r}")
    ops = 2 * macs + (n_in if layer.kind == "mean" else 0)
    return ops * batch, ((n_in + n_out) * batch + n_w) * dtype_bytes


Work = Callable[..., Tuple[int, int]]


def work_of(ref) -> Work:
    """The work counter for a reference module's layers: its own
    ``layer_work`` first where it has one, else :func:`layer_work`."""
    own: Optional[Callable] = getattr(ref, "layer_work", None)
    if own is None:
        return layer_work

    def work(layer, dtype_bytes: int, batch: int = 1) -> Tuple[int, int]:
        got = own(layer, dtype_bytes, batch)
        return layer_work(layer, dtype_bytes, batch) if got is None else got
    return work


def ops_per_image(layers: Iterable, dtype_bytes: int,
                  work: Work = layer_work) -> int:
    return sum(work(ly, dtype_bytes)[0] for ly in layers)


def ideal_s_per_call(layers: Iterable, dtype_bytes: int, batch: int,
                     peak_ops: float, hbm_bytes_per_s: float,
                     work: Work = layer_work) -> float:
    """The least time the chip could take for one call: per layer the
    larger of operations over peak and bytes over HBM bandwidth."""
    total = 0.0
    for ly in layers:
        ops, nbytes = work(ly, dtype_bytes, batch)
        total += max(ops / peak_ops, nbytes / hbm_bytes_per_s)
    return total
