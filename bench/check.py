"""The comparison that decides ``correct``.

Every answer the window produced is compared with the plain reference's
answer for the same input. A configuration file names the numbers it is
compared by, each with its limit, under ``check``; each number is the
widest reading over all answers:

- ``max_lsb`` (int8): the largest difference between an int8 class score
  and the reference's, in quantisation steps;
- ``mean_lsb`` (int8): per image, the mean difference of the class scores
  in quantisation steps; the largest over images. A fault early in the
  network moves most scores by a step or two, which the widest single
  difference can miss;
- ``max_log_gap`` (float32): the largest difference between the natural
  logarithms of a class probability and the reference's (a gap in logits,
  the same relative error for every class however small its probability).

An answer of the wrong shape or type, or with a probability that is not
positive and finite, reads as an infinite gap.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


def _lsb(got: np.ndarray, ref: np.ndarray):
    if got.dtype != np.int8:
        return None
    return np.abs(got.astype(np.int64) - ref.astype(np.int64))


def max_lsb(got: np.ndarray, ref: np.ndarray) -> float:
    d = _lsb(got, ref)
    return float("inf") if d is None else float(d.max())


def mean_lsb(got: np.ndarray, ref: np.ndarray) -> float:
    d = _lsb(got, ref)
    return float("inf") if d is None else float(d.mean(axis=-1).max())


def max_log_gap(got: np.ndarray, ref: np.ndarray) -> float:
    g = got.astype(np.float64)
    if not np.all(np.isfinite(g)) or not np.all(g > 0):
        return float("inf")
    return float(np.abs(np.log(g) - np.log(ref.astype(np.float64))).max())


NUMBERS = {"max_lsb": max_lsb, "mean_lsb": mean_lsb,
           "max_log_gap": max_log_gap}


def gaps(limits: Dict[str, float], got, ref: np.ndarray) -> Dict[str, float]:
    """Each number ``limits`` names, for one answer (any array shape)."""
    got = np.asarray(got)
    if got.shape != ref.shape:
        return {name: float("inf") for name in limits}
    return {name: NUMBERS[name](got, ref) for name in limits}


def compare(limits: Dict[str, float], answers: Sequence[Tuple[int, object]],
            refs: Dict[int, np.ndarray]) -> dict:
    """``answers``: (pool index, output) per call; ``refs``: the reference
    output per pool index. Returns each number's widest reading, how many
    answers were compared, and how many read over any limit."""
    worst = {name: 0.0 if answers else float("inf") for name in limits}
    failed = 0
    for k, out in answers:
        g = gaps(limits, out, refs[k])
        failed += any(g[n] > limits[n] for n in limits)
        worst = {n: max(worst[n], g[n]) for n in limits}
    return {"numbers": worst, "compared": len(answers), "failed": failed}
