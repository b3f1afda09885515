"""On-chip benchmark of the arena program: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration under a traffic mix. The run needs a TPU and as many chips as
the cell asks for; without them it exits non-zero and prints no result.

What a run does, in order:

1. keeps JAX's persistent compilation cache where the program's
   ``runtime.enable_compile_cache`` puts it: ``JAX_COMPILATION_CACHE_DIR``
   where that is set, else ``<checkout>/.jax_cache``;
2. builds the configuration's graph (``bench/models/<arch>.py``) and plans
   it with ``compile()`` at the traffic's batch (the plan disk cache off);
3. makes the weights and a pool of distinct inputs from ``--seed``, with
   the int8 quantisation fixed per configuration
   (``bench/reference/<arch>.py``, ``bench/loadgen.py``), and hands them
   to the program in its own types (``bench/program.py``); the
   reference's calibration is timed apart and left out of ``setup_s``;
4. warms up the cell's one call shape (set-up ends here: ``setup_s``);
5. runs the closed loop for ``--seconds``: one client calls
   ``PallasExecutor.execute`` on the traffic's route, back to back;
6. compares every answer with the plain reference (``bench/check.py``);
7. prints one JSON line: with ``--trace 0`` the cell's end-to-end
   metrics, with ``--trace 1`` its per-layer metrics, read by
   ``bench/metrics/<name>.py`` from a profiler trace of the first
   ``TRACE_CALLS`` calls (``bench/tracefile.py``).

Adding a cell needs only data: a traffic file ``bench/traffic/<name>.json``
(see ``bench/loadgen.py``), a configuration file whose ``arch`` has a graph
builder and a reference, and an entry in ``BENCHMARK.json``. A per-layer
metric is a reader module in ``bench/metrics/`` named as the metric.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: Calls the profiler records in a ``--trace 1`` run (the rest of the
#: window runs untraced): enough for a steady per-call picture, small
#: enough that the trace stays tens of MB at batch 8.
TRACE_CALLS = 20
#: Warm-up calls: the first compiles or loads the program from the cache.
WARMUP_CALLS = 3


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(2)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def tpu_devices(chips: int):
    """The chips this run may use; exits when JAX finds no TPU or fewer
    chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


class CompileCounter:
    """Counts traces and backend compiles JAX reports while ``armed``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kwargs):
        if self.armed and event in self.EVENTS:
            self.count += 1


def traced_window(call, pool, seconds: float):
    """The closed loop with the profiler on for its first ``TRACE_CALLS``
    calls. Returns the calls, their summed latency and the trace's
    summary."""
    import jax

    from bench import loadgen, tracefile
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            traced_s, traced = loadgen.closed_loop(
                call, pool, seconds, max_calls=TRACE_CALLS,
                annotate=lambda i: jax.profiler.TraceAnnotation(
                    tracefile.CALL_SPAN))
        finally:
            jax.profiler.stop_trace()
        rest_s, rest = loadgen.closed_loop(call, pool, seconds - traced_s,
                                           first=len(traced))
        paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp)
                 for f in fs if f.endswith(".xplane.pb")]
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, got {paths}")
        summary = tracefile.summarise(tracefile.load(paths[0]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return traced + rest, traced_s + rest_s, summary


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, executor=None) -> dict:
    """One run of ``cell``; returns the result line's object. ``executor``
    replaces the route's ``PallasExecutor`` (tests drive the harness on a
    CPU with a stand-in)."""
    import numpy as np

    from bench import check, loadgen, opcount, peaks, program, registry

    to_cell_s = time.perf_counter() - t_start
    program.import_program()
    cfg, traffic = cell.config, cell.traffic
    batch, dtype = int(traffic["batch"]), cfg["dtype"]
    ref = cell.reference()
    readers = registry.metric_readers(cell.per_layer) if trace else {}
    row = peaks.peaks(devices[0].device_kind) if trace else None

    t = time.perf_counter()
    cp = program.compile_graph(cell.model().graph(cfg), batch)
    plan_s = time.perf_counter() - t

    in_name, out_name = program.io_names(cp)
    image_shape = next(t.shape for t in cp.graph.tensors
                       if t.name == in_name)
    pool = loadgen.make_pool(traffic, image_shape, dtype, seed)
    t = time.perf_counter()
    calib = ref.calibrate(cfg)
    calib_s = time.perf_counter() - t
    weights, quant = ref.make_params(cfg, seed, calib)
    p_weights, p_quant = program.program_params(cp, weights, quant)
    be = executor or program.executor(traffic["route"])

    def call(x):
        return be.execute(cp, {in_name: x}, p_weights,
                          quant=p_quant)[out_name]

    warm = []
    for i in range(WARMUP_CALLS):
        t = time.perf_counter()
        call(pool[i % len(pool)])
        warm.append(round(time.perf_counter() - t, 4))
    setup_s = time.perf_counter() - t_start - calib_s

    counter = CompileCounter()
    counter.armed = True
    if trace:
        calls, span, summary = traced_window(call, pool, seconds)
    else:
        span, calls = loadgen.closed_loop(call, pool, seconds)
    counter.armed = False

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)}
    arena_kb = program.arena_kb(cp)
    del be, cp, p_weights, p_quant, call

    # the reference, once the window has closed and the program is freed
    refs = {}
    for k in sorted({k for k, _, _ in calls}):
        refs[k] = (np.stack([ref.predict(cfg, weights, quant, img)
                             for img in pool[k]]) if batch > 1
                   else ref.predict(cfg, weights, quant, pool[k]))
    limits = cfg["check"]
    verdict = check.compare(limits, [(k, out) for k, _, out in calls], refs)

    images = len(calls) * batch
    lat = [lat for _, lat, _ in calls]
    if trace:
        width = opcount.WIDTH[dtype]
        peak = peaks.compute_peak(row, dtype)
        layers, work = ref.layers(cfg), opcount.work_of(ref)
        ctx = {"plan_s": plan_s, "batch": batch, "trace": summary,
               "images_per_s": images / sum(lat), "peak_ops": peak,
               "ops_per_image": opcount.ops_per_image(layers, width, work),
               "ideal_s_per_call": opcount.ideal_s_per_call(
                   layers, width, batch, peak, row["hbm_bytes_per_s"],
                   work)}
        values = {m["name"]: readers[m["name"]].read(ctx)
                  for m in cell.per_layer}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        values = {"images_per_s": images / span,
                  "latency_p95_ms": float(np.percentile(lat, 95)) * 1e3,
                  "arena_kb": arena_kb, "setup_s": setup_s}
    result = {
        "correct": verdict["failed"] == 0 and verdict["compared"] > 0,
        "attempted": len(calls),
        "failed": verdict["failed"],
        "metrics": {m["name"]: {"value": values[m["name"]],
                                "unit": m["unit"]}
                    for m in (cell.per_layer if trace else cell.end_to_end)
                    if values[m["name"]] is not None},
        "device": device,
    }
    if trace:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["compared"] = {n: {"value": v, "limit": limits[n]}
                          for n, v in verdict["numbers"].items()}
    print(f"bench: {cell.name} seed {seed}: {len(calls)} calls, "
          f"{counter.count} traces or compiles inside the window; set-up "
          f"{setup_s:.3f} s: to the cell {to_cell_s:.3f}, plan "
          f"{plan_s:.3f}, warm-up calls {warm}; reference calibration "
          f"{calib_s:.3f} s left out", file=sys.stderr)
    return result


def main(argv=None) -> None:
    args = parse(argv)
    from bench import registry
    cell = registry.load_cell(args.workload)
    devices = tpu_devices(cell.chips)[:cell.chips]
    try:
        from bench import program
        program.import_program()
    except ImportError as e:
        fail(f"the program is not in this checkout: {e}")
    from repro.kernels import runtime
    runtime.enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_START)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
