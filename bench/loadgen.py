"""The general load generator: inputs and the closed loop, from a traffic file.

One closed-loop client sends its next call as soon as the last returns.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

- ``batch``: images per call;
- ``pool_calls``: distinct calls in the seeded input pool, which the loop
  cycles through in order;
- ``route``: the executor route the client calls (``compiled`` keeps the
  whole arena VMEM-resident; ``streaming`` keeps it in HBM and DMAs live
  windows).
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional, Tuple

import numpy as np

TRAFFIC_KEYS = {"batch", "pool_calls", "route"}


def validate(traffic: dict) -> dict:
    """Refuse a traffic file with missing or unknown keys or values this
    generator cannot drive."""
    keys = set(traffic) - {"why"}
    if keys != TRAFFIC_KEYS:
        raise ValueError(f"traffic keys {sorted(keys)} != "
                         f"{sorted(TRAFFIC_KEYS)}")
    if traffic["batch"] < 1 or traffic["pool_calls"] < 1:
        raise ValueError("batch and pool_calls must be >= 1")
    return traffic


def make_pool(traffic: dict, image_shape: Tuple[int, ...], dtype: str,
              seed: int) -> List[np.ndarray]:
    """``pool_calls`` distinct call inputs from ``seed``: int8 pixels for
    int8 tiers, float32 in [-1, 1) otherwise. A batch-1 call is one image
    ``image_shape``; a batch-b call is ``(b,) + image_shape``."""
    rng = np.random.default_rng([seed, 2])
    b = int(traffic["batch"])
    shape = ((b,) if b > 1 else ()) + tuple(image_shape)
    pool = []
    for _ in range(int(traffic["pool_calls"])):
        if dtype == "int8":
            pool.append(rng.integers(-128, 128, shape, dtype=np.int8))
        else:
            pool.append(rng.uniform(-1.0, 1.0, shape).astype(np.float32))
    return pool


def pool_images(pool: List[np.ndarray], batch: int) -> List[np.ndarray]:
    """The pool's images in call order, one array per image."""
    return [img for x in pool for img in (x if batch > 1 else [x])]


def closed_loop(call: Callable[[np.ndarray], np.ndarray],
                pool: List[np.ndarray], seconds: float,
                annotate: Optional[Callable[[int], object]] = None,
                max_calls: Optional[int] = None, first: int = 0):
    """Send calls back to back, cycling through ``pool`` from call number
    ``first``, while less than ``seconds`` have passed since the first was
    sent (and, if given, until ``max_calls`` have been sent). Returns the
    window's seconds, from the first call's start to the last call's end,
    and per call ``(pool index, latency s, output)``."""
    calls = []
    t0 = time.perf_counter()
    end = t0 + seconds
    t = t0
    i = first
    while t < end and (max_calls is None or len(calls) < max_calls):
        k = i % len(pool)
        if annotate is None:
            out = call(pool[k])
        else:
            with annotate(i):
                out = call(pool[k])
        t1 = time.perf_counter()
        calls.append((k, t1 - t, out))
        t = t1
        i += 1
    return t - t0, calls
