"""Smoke test of the arena program on one TPU chip.

Compiles the paper's flagship network and a full-width float network with
``compile(graph, backend="pallas")``, runs each through the Pallas arena
program (``CompiledPlan.execute()``, or the streaming executor), and checks
the outputs against the numpy arena backend on the same seeded inputs and
weights (f32 to the shared fp32 tolerance, int8 to <= 1 LSB).

    python chip_smoke.py

Phases (all in this one process):

- ``mobilenet_v1_0.25_128_8bit`` at batch 1 and batch 8, compiled route
  (each launch stages its operands' rows into a packed VMEM buffer);
- the same model at batch 1 on the streaming route (its convs, depthwise
  convs and pools on the roll grid, every other launch staged alike);
- ``mobilenet_v1_1.0_224`` f32 at batch 1, compiled route.

Each phase that passes prints one JSON line; a failing phase prints its
traceback and the rest still run. The last line of the output is
``{"ok": true, "device": {...}}`` only when JAX runs on a TPU and every
phase ran and held parity; otherwise the script exits non-zero.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

#: (model key in zoo.TABLE3_MODELS, batch, route)
PHASES = (
    ("mobilenet_v1_0.25_128_8bit", 1, "compiled"),
    ("mobilenet_v1_0.25_128_8bit", 8, "compiled"),
    ("mobilenet_v1_0.25_128_8bit", 1, "streaming"),
    ("mobilenet_v1_1.0_224", 1, "compiled"),
)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def run_phase(name: str, batch: int, route: str) -> dict:
    import numpy as np

    from repro.core import exec as X
    from repro.core import zoo
    from repro.core.pipeline import compile as compile_graph

    t0 = time.perf_counter()
    cp = compile_graph(zoo.TABLE3_MODELS[name][0](), batch=batch,
                       backend="pallas")
    plan_s = time.perf_counter() - t0
    be = X.get_backend("pallas", mode=route)
    if be.interpret:
        raise RuntimeError("the chip must run compiled kernels")
    t0 = time.perf_counter()
    got = cp.execute() if route == "compiled" else be.execute(cp)
    first_s = time.perf_counter() - t0       # lowering + Mosaic + one run
    ref = X.get_backend("numpy").execute(cp)
    diff = max(float(np.max(np.abs(np.asarray(got[k], np.float64)
                                   - np.asarray(ref[k], np.float64))))
               for k in ref)
    X.compare_outputs(ref, got, exact=False, label=f"{name} b{batch} {route}")
    # the launch count of the program just run (same seeded calibration)
    weights = X.synth_weights(cp.graph, 0)
    quant = (X.calibrate(cp.graph, 0, weights)
             if X.needs_quant(cp.graph) else None)
    bp = cp.legalised()
    lower = be.lower_stream if route == "streaming" else be.lower_blocks
    return {
        "model": name, "dtype": "int8" if quant else "f32", "batch": batch,
        "route": route, "launches": len(lower(bp, quant)),
        "arena": [bp.total_rows, bp.arena_rowlen],
        "plan_s": round(plan_s, 3),
        "compile_and_first_run_s": round(first_s, 3),
        "max_abs_diff_vs_numpy": diff,
        "parity": "<=1 LSB" if quant else "fp32 tolerance",
    }


def main() -> None:
    try:
        import jax
    except ImportError as e:
        fail(f"JAX is not importable: {e}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX runs on {dev.platform!r}")
    try:
        from repro.kernels.runtime import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not beside this script: {e}")
    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    failed = []
    for name, batch, route in PHASES:
        try:
            row = run_phase(name, batch, route)
        except Exception as e:   # record the phase, run the rest, then fail
            traceback.print_exc()
            failed.append(f"{name} batch {batch} {route}: "
                          f"{type(e).__name__}: {e}")
            continue
        print(json.dumps(row), flush=True)
    if failed:
        fail("; ".join(failed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
