"""Executor backends: run a planned arena on a real runtime.

A :class:`~repro.core.planner.Plan` (or a
:class:`~repro.core.pipeline.CompiledPlan`) describes ONE flat arena —
offsets plus the safe diagonal overlaps ``O_s`` — and the paper's claim is
that it is *executable*: ops walk output rows in ascending order inside the
shared buffer and never clobber a live value. This package turns that claim
into a pluggable runtime layer:

- ``numpy``  — :mod:`.numpy_backend`: the row-by-row NumPy interpreter
  (bit-exact ground truth, used by ``verify_plan``);
- ``pallas`` — :mod:`.pallas_backend`: lowers the plan to a sequence of
  Pallas kernels over one donated arena buffer (``input_output_aliases``
  threads the arena through the op sequence). Three programs: the
  **row-blocked** 2-D arena (plans legalised onto per-dtype VMEM tiles by
  :func:`repro.core.planner.legalise_for_blocks` — the compiled-mode path,
  and the default whenever the plan legalises), the **streaming** grid
  program (``mode="streaming"``: arena in HBM, each op's live window
  DMA'd into VMEM scratch per the planner's
  :meth:`~repro.core.planner.BlockPlan.window_schedule`, VMEM-gated on
  the window instead of the whole arena), and the **flat** byte arena
  (interpret-only fallback for mixed-dtype plans, and the cross-check
  reference). ``mode="interpret"`` runs any of them in the Pallas
  interpreter on the host CPU; ``mode="compiled"`` lowers the blocked
  program through Mosaic on a TPU — the TPU analogue of the paper's SRAM
  arena being VMEM. Unpinned, the mode follows the platform. Select per
  instance via
  ``get_backend("pallas", mode=..., layout=...)``.

Every backend implements the :class:`ArenaExecutor` protocol::

    outputs = get_backend("pallas").execute(plan_or_compiled, inputs, weights)

``inputs``/``weights`` default to the deterministic synthesis of
:mod:`repro.core.exec.ops`, so two backends handed the same (plan, seed)
execute the identical network and can be diffed output-for-output
(:func:`cross_check`).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Protocol, Tuple

import numpy as np

from repro.core.exec import ops
from repro.core.exec.ops import (ELEMENTWISE, SUPPORTED_DTYPES,
                                 SUPPORTED_KINDS, OpQuant, QParams, QuantSpec,
                                 arena_dtype, calibrate, executability,
                                 executable, needs_quant, op_quant,
                                 quant_inputs, random_inputs, synth_weights)
from repro.core.graph import Graph
from repro.core.planner import Plan


class ArenaExecutor(Protocol):
    """An executor backend: runs a planned graph inside its flat arena."""

    #: registry name ("numpy", "pallas", ...)
    name: str

    def execute(self, plan_or_compiled, inputs=None, weights=None, *,
                seed: int = 0, quant=None) -> Dict[str, np.ndarray]:
        """Execute ``plan_or_compiled`` (a Plan or CompiledPlan) and return
        the model outputs keyed by tensor name. ``inputs`` / ``weights``
        default to the deterministic per-seed synthesis shared by all
        backends; ``quant`` is the :class:`~repro.core.exec.ops.QuantSpec`
        for int8 graphs (auto-calibrated when omitted)."""
        ...


def unwrap_plan(plan_or_compiled) -> Tuple[Plan, Graph]:
    """Accept a Plan or a CompiledPlan; return (plan, executed graph)."""
    if isinstance(plan_or_compiled, Plan):
        return plan_or_compiled, plan_or_compiled.graph
    plan = getattr(plan_or_compiled, "plan", None)
    if isinstance(plan, Plan):
        return plan, plan.graph
    raise TypeError(f"expected Plan or CompiledPlan, got "
                    f"{type(plan_or_compiled).__name__}")


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

_FACTORIES: Dict[str, Callable[..., ArenaExecutor]] = {}
_INSTANCES: Dict[str, ArenaExecutor] = {}


def register_backend(name: str, factory: Callable[..., ArenaExecutor]) -> None:
    _FACTORIES[name] = factory
    _INSTANCES.pop(name, None)  # re-registration must not serve a stale one


def available_backends() -> Tuple[str, ...]:
    return tuple(_FACTORIES)


def get_backend(name: str, **kwargs: Any) -> ArenaExecutor:
    """Backend instance by name. Default-configured instances are cached;
    passing kwargs constructs a fresh one."""
    if name not in _FACTORIES:
        raise ValueError(
            f"unknown executor backend {name!r}; available: "
            f"{available_backends()}")
    if kwargs:
        return _FACTORIES[name](**kwargs)
    if name not in _INSTANCES:
        _INSTANCES[name] = _FACTORIES[name]()
    return _INSTANCES[name]


def _numpy_factory(**kw) -> ArenaExecutor:
    from repro.core.exec.numpy_backend import NumpyExecutor
    return NumpyExecutor(**kw)


def _pallas_factory(**kw) -> ArenaExecutor:
    # imported lazily: the core planning path must not pay the jax import
    from repro.core.exec.pallas_backend import PallasExecutor
    return PallasExecutor(**kw)


register_backend("numpy", _numpy_factory)
register_backend("pallas", _pallas_factory)


# ---------------------------------------------------------------------------
# Cross-backend verification
# ---------------------------------------------------------------------------

#: fp32 tolerance for backends whose accumulations XLA may reassociate
#: relative to the numpy loop order. The single source of truth — the verify
#: pass, verify_plan and cross_check all compare through it.
FP32_RTOL = 1e-4
FP32_ATOL = 1e-4
#: Integer (int8) outputs tolerate one least-significant quantisation step:
#: transcendental ulp differences (exp in softmax/sigmoid) can flip a round.
INT8_ATOL = 1


def compare_outputs(ref: Dict[str, np.ndarray], got: Dict[str, np.ndarray],
                    exact: bool, label: str) -> None:
    """Assert two output dicts match: bit-exact, or at the shared tolerance
    for the output's dtype (fp32 atol/rtol for float outputs, <= 1 LSB for
    quantised int8 outputs). Raises ``AssertionError`` on any mismatch."""
    assert ref.keys() == got.keys(), f"{label}: output sets differ"
    for k in ref:
        if exact:
            np.testing.assert_array_equal(got[k], ref[k],
                                          err_msg=f"output {k} ({label})")
        elif np.issubdtype(np.asarray(ref[k]).dtype, np.integer):
            np.testing.assert_allclose(
                np.asarray(got[k]).astype(np.int32),
                np.asarray(ref[k]).astype(np.int32),
                rtol=0, atol=INT8_ATOL, err_msg=f"output {k} ({label})")
        else:
            np.testing.assert_allclose(got[k], ref[k], rtol=FP32_RTOL,
                                       atol=FP32_ATOL,
                                       err_msg=f"output {k} ({label})")


def cross_check(plan_or_compiled, seed: int = 0,
                backends: Tuple = ("numpy", "pallas")) -> None:
    """Execute the plan on both backends with identical inputs/weights (and,
    for int8 graphs, one shared calibration) and assert the arena outputs
    agree — fp32 tolerance where XLA may reassociate the dot-product
    accumulations the numpy semantics run in loop order, <= 1 LSB on
    quantised outputs. Raises ``AssertionError`` on any mismatch. Entries of
    ``backends`` are registry names or pre-configured executor instances
    (e.g. ``get_backend("pallas", layout="flat")``), so differently-laid-out
    programs of one backend can be diffed too."""
    plan, graph = unwrap_plan(plan_or_compiled)
    reason = executability(graph)
    if reason is not None:
        raise ValueError(f"graph is not executable by arena backends: {reason}")
    weights = synth_weights(graph, seed)
    quant = calibrate(graph, seed, weights) if needs_quant(graph) else None
    inputs = (quant_inputs(graph, quant, seed) if quant is not None
              else random_inputs(graph, seed))
    resolve = lambda b: b if hasattr(b, "execute") else get_backend(b)
    label = lambda b: b if isinstance(b, str) else getattr(b, "name", str(b))
    a = resolve(backends[0]).execute(plan, inputs, weights, seed=seed,
                                     quant=quant)
    b = resolve(backends[1]).execute(plan, inputs, weights, seed=seed,
                                     quant=quant)
    compare_outputs(a, b, exact=False,
                    label=f"{label(backends[1])} vs {label(backends[0])}")


__all__ = [
    "ArenaExecutor", "ELEMENTWISE", "FP32_ATOL", "FP32_RTOL", "INT8_ATOL",
    "arena_dtype",
    "OpQuant", "QParams", "QuantSpec", "SUPPORTED_DTYPES", "SUPPORTED_KINDS",
    "available_backends", "calibrate", "compare_outputs", "cross_check",
    "executability", "executable", "get_backend", "needs_quant", "op_quant",
    "ops", "quant_inputs", "random_inputs", "register_backend",
    "synth_weights", "unwrap_plan",
]
