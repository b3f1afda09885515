"""The system under test, as the harness drives it.

Everything here speaks the program's API: build a configuration's graph
through ``bench/models/<arch>.py``, ``compile()`` it, hand the harness's own
weights and quantisation parameters to the executor in the program's types,
and call ``PallasExecutor.execute`` on the cell's route. Nothing the program
makes flows back into the reference.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Executor routes a traffic file may name -> ``get_backend`` options.
ROUTES = {"compiled": {}, "streaming": {"mode": "streaming"}}


def import_program():
    """Put the program's sources on the path; raises ImportError where the
    checkout holds only the benchmark."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.core.pipeline  # noqa: F401  (fails without the program)


def compile_graph(graph, batch: int):
    """The planner's entry: the whole pass chain at the cell's batch, with
    the plan disk cache left off so that every run plans."""
    from repro.core.pipeline import compile as compile_plan
    return compile_plan(graph, batch=batch, backend="pallas",
                        disk_cache=False)


def executor(route: str):
    """One executor instance for the run, on the traffic's route."""
    from repro.core import exec as X
    if route not in ROUTES:
        raise ValueError(f"unknown route {route!r} (known: {sorted(ROUTES)})")
    be = X.get_backend("pallas", **ROUTES[route])
    if be.interpret:
        raise RuntimeError("the benchmark must run compiled kernels")
    return be


def io_names(cp) -> Tuple[str, str]:
    """(input tensor, output tensor) names of the executed graph."""
    ins = [t.name for t in cp.graph.tensors if t.kind == "input"]
    outs = [t.name for t in cp.graph.tensors if t.kind == "output"]
    if len(ins) != 1 or len(outs) != 1:
        raise ValueError(f"expected one input and one output, got {ins} "
                         f"and {outs}")
    return ins[0], outs[0]


def arena_kb(cp) -> float:
    """Bytes of the typed arena the executed program runs in, / 1024."""
    bp = cp.legalised()
    return bp.total_rows * bp.row_bytes / 1024.0


def source_layer(cp) -> Dict[str, str]:
    """Executed-graph tensor name -> the layer (reference op name) whose
    output it holds, or ``"input"``. Split row bands carry their source op
    in ``split_src``; the concat that joins them writes the source tensor,
    which keeps its name from the original graph."""
    produced = {op.output.name: op.name for op in cp.original.ops}
    by_out = {op.output.storage().name: op for op in cp.graph.ops}
    out = {}
    for t in cp.graph.data_tensors():
        if t.kind == "input":
            out[t.name] = "input"
        elif t.name in produced:
            out[t.name] = produced[t.name]
        else:
            out[t.name] = by_out[t.name].params["split_src"]
    return out


def op_layer(op) -> str:
    """The reference layer an executed op computes (part of)."""
    return op.params.get("split_src", op.name)


def program_params(cp, weights, quant):
    """The harness's weights (float32, keyed by layer) and quantisation
    (``reference.Quant`` or None) in the program's types: weights keyed by
    ``id(op)`` of the executed graph, a ``QuantSpec`` keyed by its tensor
    names."""
    from repro.core.exec.ops import QParams, QuantSpec
    w = {id(op): ({"filter": weights[op_layer(op)]}
                  if op_layer(op) in weights else {}) for op in cp.graph.ops}
    if quant is None:
        return w, None
    layer_of = source_layer(cp)
    tensors = {name: QParams(*quant.act[layer])
               for name, layer in layer_of.items()}
    wscale = {id(op): quant.wscale[op_layer(op)] for op in cp.graph.ops
              if op_layer(op) in quant.wscale}
    wq = {id(op): {"filter": quant.wq[op_layer(op)]} for op in cp.graph.ops
          if op_layer(op) in quant.wq}
    return w, QuantSpec(tensors, wscale, wq)
