"""Export one arena execution as a chrome://tracing JSON file.

Runs the compiled plan on the selected execution route and writes:

- one ``"X"`` duration span per *launch* (per op on the numpy route, per
  lowered spec — i.e. per ``pallas_call`` — on the pallas routes, so a
  fused band chain shows as ONE span with its stage count), with the
  launch's arena byte/row range and, when the plan legalises, its streaming
  live window ``[lo, hi)`` in arena rows;
- ``"C"`` counter tracks: ``arena_live_bytes`` (numpy route: bytes of the
  byte arena occupied by tensors live at each step — the planner's
  occupancy curve), ``arena_padded_bytes`` (the same liveness costed in
  the legalised row-blocked layout — whole padded arena rows per tensor,
  so the gap between the two curves IS the lane-padding tax the packed
  layouts shrink), ``window_rows`` (each op's streaming VMEM-resident
  rows), and ``pallas_launches`` (pallas routes: cumulative launch count).

Routes:

- ``numpy``     — op-by-op on the numpy arena interpreter (reference);
- ``flat``      — the flat byte Pallas program (interpret mode);
- ``blocked``   — the row-blocked typed Pallas program;
- ``streaming`` — the double-buffered streaming Pallas program;
- ``fused``     — alias of ``blocked`` that *requires* the winning graph to
  carry fused band chains (errors out otherwise), for eyeballing the
  one-launch-per-chain collapse;
- ``serve``     — a closed-loop :class:`~repro.serve.PlanServer` run:
  request-level spans (queue wait -> batch assembly -> execute), one
  trace track per request, so the deadline-batching behaviour is visible
  request by request.

Open the file at ``chrome://tracing`` (or https://ui.perfetto.dev).

Usage::

    PYTHONPATH=src python scripts/export_trace.py            # reduced model
    PYTHONPATH=src python scripts/export_trace.py \
        --model mobilenet_v1_0.25_128_8bit --route fused --out trace.json
"""
from __future__ import annotations

import argparse
import json
import time

ROUTES = ("numpy", "flat", "blocked", "streaming", "fused", "serve")


def _build(name: str):
    from repro.core import zoo
    if name in zoo.TABLE3_MODELS:
        return zoo.TABLE3_MODELS[name][0]()
    if name == "mobilenet_v1_0.25_32_8bit":
        return zoo.mobilenet_v1(0.25, 32, 1)
    if name == "mobilenet_v1_0.25_32_f32":
        return zoo.mobilenet_v1(0.25, 32, 4)
    raise SystemExit(f"unknown model {name!r}: pick a TABLE3_MODELS name, "
                     "'mobilenet_v1_0.25_32_8bit' or "
                     "'mobilenet_v1_0.25_32_f32'")


def _autoparams(graph):
    from repro.core import exec as X
    weights = X.synth_weights(graph)
    quant = X.calibrate(graph, 0, weights) if X.needs_quant(graph) else None
    inputs = (X.quant_inputs(graph, quant) if quant is not None
              else X.random_inputs(graph))
    return weights, quant, inputs


def trace_events(cp) -> list:
    """Chrome-tracing events for one op-by-op arena execution of ``cp``
    (a :class:`~repro.core.pipeline.CompiledPlan`) on the numpy route."""
    from repro.core.exec.numpy_backend import ArenaExec

    plan, graph = cp.plan, cp.graph
    weights, quant, inputs = _autoparams(graph)
    ex = ArenaExec(graph, plan, inputs, weights=weights, quant=quant)

    scopes = graph.scopes(plan.order)
    windows = {}
    bp = cp.legalised()
    if bp is not None:
        windows = {w.op_name: w for w in bp.window_schedule().windows}

    events, t0 = [], time.perf_counter()
    for step, op in enumerate(plan.order):
        ts = (time.perf_counter() - t0) * 1e6
        ex.execute(op)
        dur = (time.perf_counter() - t0) * 1e6 - ts
        args = {"kind": op.kind, "step": step}
        s = op.output.storage()
        if s in plan.offsets:
            args["arena_bytes"] = [plan.offsets[s],
                                   plan.offsets[s] + s.nbytes]
        w = windows.get(op.name)
        if w is not None:
            args["window_rows"] = [w.lo, w.hi]
            args["resident_rows"] = w.resident_rows
        events.append({"name": op.name, "cat": op.kind, "ph": "X",
                       "ts": round(ts, 3), "dur": round(max(dur, 0.001), 3),
                       "pid": 1, "tid": 1, "args": args})
        live = sum(t.nbytes for t, (s0, e0) in scopes.items()
                   if s0 <= step <= e0)
        events.append({"name": "arena_live_bytes", "ph": "C",
                       "ts": round(ts, 3), "pid": 1,
                       "args": {"bytes": int(live)}})
        if bp is not None:
            # what the same liveness costs in the legalised (row-blocked,
            # possibly packed) layout: whole padded arena rows per tensor
            padded = sum(bp.layouts[t].rows * bp.row_bytes
                         for t, (s0, e0) in scopes.items()
                         if s0 <= step <= e0 and t in bp.layouts)
            events.append({"name": "arena_padded_bytes", "ph": "C",
                           "ts": round(ts, 3), "pid": 1,
                           "args": {"bytes": int(padded)}})
        if w is not None:
            events.append({"name": "window_rows", "ph": "C",
                           "ts": round(ts, 3), "pid": 1,
                           "args": {"rows": int(w.resident_rows)}})
    return events


def _launch_names(order) -> list:
    """One display name per lowered spec, mirroring the backend's lowering
    order: reshapes dropped, a fused chain collapsed to its chain name at
    the first member's position."""
    names, emitted = [], set()
    for op in order:
        if op.kind == "reshape":
            continue
        cname = op.params.get("fuse_chain")
        if cname is None:
            names.append(op.name)
        elif cname not in emitted:
            emitted.add(cname)
            names.append(cname)
    return names


def trace_pallas_events(cp, route: str) -> list:
    """Chrome-tracing events for one launch-by-launch pallas execution of
    ``cp`` — each span is one ``pallas_call`` (a fused chain = one span).
    The kernels run in the Pallas interpreter on the host CPU, so the
    spans are host-clock spans and no TPU is touched."""
    import jax
    from repro.kernels import runtime
    with jax.default_device(runtime.interpret_device()):
        return _interpreted_pallas_events(cp, route)


def _interpreted_pallas_events(cp, route: str) -> list:
    import jax.numpy as jnp
    import numpy as np
    from repro.core import exec as X
    from repro.core.exec.pallas_backend import PallasExecutor
    from repro.kernels import arena_ops

    plan, graph = cp.plan, cp.graph
    weights, quant, inputs = _autoparams(graph)
    bp = cp.legalised()
    windows = {}

    if route == "flat":
        be = PallasExecutor(layout="flat", interpret=True)
        specs = be.lower(plan, quant)
        arena = np.zeros(plan.peak_bytes, np.uint8)
        for t in graph.tensors:
            if t.kind == "input":
                s, off = t.storage(), plan.offsets[t.storage()]
                v = np.asarray(inputs[t.name],
                               X.arena_dtype(s.dtype_bytes)).reshape(-1)
                arena[off:off + s.nbytes] = v.view(np.uint8)
    else:
        if bp is None:
            raise SystemExit(
                f"--route {route} needs a legalisable plan and "
                f"{graph.name!r} does not legalise for blocks")
        if route == "fused" and not any(
                "fuse_chain" in op.params for op in bp.order):
            raise SystemExit(
                f"--route fused: {graph.name!r} carries no fused band "
                "chains (compile picked an unfused variant)")
        if route == "streaming":
            be = PallasExecutor(mode="streaming", interpret=True)
            specs = be.lower_stream(bp, quant)
            windows = {w.op_name: w for w in bp.window_schedule().windows}
        else:
            be = PallasExecutor(layout="blocks", interpret=True)
            specs = be.lower_blocks(bp, quant)
        arena = PallasExecutor._seed_block_arena(bp, graph, inputs)

    wflat = []
    for op in plan.order:
        if op.kind in arena_ops.WEIGHTED_KINDS:
            if quant is not None and id(op) in quant.weights_q:
                wflat.append(jnp.asarray(quant.weights_q[id(op)]["filter"],
                                         jnp.int8))
            else:
                wflat.append(jnp.asarray(weights[id(op)]["filter"],
                                         jnp.float32))

    names = _launch_names(plan.order)
    assert len(names) == len(specs), (len(names), len(specs))

    events, t0 = [], time.perf_counter()
    buf, wi = jnp.asarray(arena), 0
    for step, (name, spec) in enumerate(zip(names, specs)):
        nw = arena_ops.spec_weight_count(spec)
        ws = tuple(wflat[wi:wi + nw])
        wi += nw
        ts = (time.perf_counter() - t0) * 1e6
        buf = arena_ops.apply_op(buf, spec, ws, interpret=True)
        buf.block_until_ready()
        dur = (time.perf_counter() - t0) * 1e6 - ts
        args = {"kind": spec.kind, "step": step, "route": route}
        if spec.kind == "fused":
            args["stages"] = len(spec.stages)
            args["scratch_rows"] = spec.scratch_rows
        if spec.rowlen:
            args["arena_rows"] = [spec.out_off,
                                  spec.out_off + (spec.out_rows[0]
                                                  if spec.out_rows else 0)]
        else:
            args["arena_bytes"] = [spec.out_off, spec.out_off]
        w = windows.get(name)
        if w is not None:
            args["window_rows"] = [w.lo, w.hi]
            args["resident_rows"] = w.resident_rows
        events.append({"name": name, "cat": spec.kind, "ph": "X",
                       "ts": round(ts, 3), "dur": round(max(dur, 0.001), 3),
                       "pid": 1, "tid": 1, "args": args})
        events.append({"name": "pallas_launches", "ph": "C",
                       "ts": round(ts, 3), "pid": 1,
                       "args": {"launches": step + 1}})
        if w is not None:
            events.append({"name": "window_rows", "ph": "C",
                           "ts": round(ts, 3), "pid": 1,
                           "args": {"rows": int(w.resident_rows)}})
    return events


def trace_serve_events(graph, n_requests: int = 64) -> list:
    """Chrome-tracing events for a closed-loop PlanServer run: each request
    is one trace track (tid = request id) carrying its queue-wait, batch-
    assembly and execute spans, plus a queue-depth counter per flush."""
    import numpy as np
    from repro.serve import PlanServer

    server = PlanServer(graph)
    rng = np.random.default_rng(1)
    shapes = {t.name: tuple(t.shape)
              for t in graph.tensors if t.kind == "input"}
    for _ in range(n_requests):
        server.submit({nm: rng.standard_normal(sh).astype(np.float32)
                       for nm, sh in shapes.items()})
        server.step()
    server.drain()

    events = []
    for s in server.spans():
        ts = s["t_submit"] * 1e6
        for phase in ("queue_wait", "assemble", "execute"):
            dur = s[f"{phase}_s"] * 1e6
            events.append({
                "name": phase, "cat": "serve", "ph": "X",
                "ts": round(ts, 3), "dur": round(max(dur, 0.001), 3),
                "pid": 1, "tid": s["rid"],
                "args": {"rid": s["rid"], "batch": s["batch"]}})
            ts += dur
    st = server.stats()
    events.append({"name": "serve_stats", "ph": "C", "ts": 0.0, "pid": 1,
                   "args": {"throughput_inf_s": st["throughput_inf_s"] or 0}})
    return events


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="export an arena execution as chrome://tracing JSON")
    ap.add_argument("--model", default="mobilenet_v1_0.25_32_8bit")
    ap.add_argument("--route", default="numpy", choices=ROUTES,
                    help="execution route to trace (default: numpy)")
    ap.add_argument("--requests", type=int, default=64,
                    help="request count for --route serve (default 64)")
    ap.add_argument("--out", default="trace.json")
    args = ap.parse_args(argv)

    from repro.core.pipeline import compile as compile_graph
    cp = compile_graph(_build(args.model))
    if args.route == "numpy":
        events = trace_events(cp)
    elif args.route == "serve":
        events = trace_serve_events(cp.original, args.requests)
    else:
        events = trace_pallas_events(cp, args.route)
    spans = sum(1 for e in events if e["ph"] == "X")
    with open(args.out, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                   "otherData": {"model": args.model, "route": args.route,
                                 "peak_bytes": cp.peak_bytes}}, f)
        f.write("\n")
    print(f"wrote {args.out}: {len(events)} events, {spans} launches "
          f"over {len(cp.plan.order)} ops ({args.route} route)")


if __name__ == "__main__":
    main()
