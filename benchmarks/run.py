"""Benchmark harness: one module per paper table/figure + framework extras.
Prints ``name,us_per_call,derived`` CSV rows.

``--json [PATH]`` additionally writes a structured artifact (default
``BENCH_pr10.json``): per-model plan peaks (fixed-order vs joint
execution-order x overlap search, plus the order-search wall time),
blocked/window rows, the shipped layout's packing (packed peak, padding
overhead, the legacy layout's cost for comparison), pallas launch counts
(fused band chains collapse to one), compile time, the memory-vs-batch
trade curve (``peak_vs_batch``), exec throughput per backend×dtype, and
the serving demo's sustained inferences/sec (``serve_throughput``) — so
the perf trajectory is machine-readable instead of living in prose. ``--sweep off`` skips the CSV sweep when only
the artifact is wanted. ``scripts/bench_diff.py`` diffs two artifacts and
fails on regressions (the CI perf gate).

Benchmark reruns start warm: the compile plan cache persists to disk
(content-addressed by graph signature under ``$REPRO_DMO_CACHE_DIR``,
default ``~/.cache/repro-dmo``) — set ``REPRO_DMO_DISK_CACHE=0`` to force
cold planning. The sweep reports the cache's memory and disk hit/miss
counters when it finishes."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _json_payload(rows):
    """The ``--json`` artifact: plan-level stats for every Table III model
    (peaks, blocked rows, streaming window rows, compile time) plus exec
    throughput per backend×dtype on reduced executable builds."""
    from repro.core import exec as X
    from repro.core import zoo
    from repro.core.pipeline import cache_info, compile as compile_graph

    models = {}
    for name, (build, paper_orig, paper_opt) in zoo.TABLE3_MODELS.items():
        t0 = time.perf_counter()
        cp = compile_graph(build(), profile="paper", method="algorithmic",
                           budget_s="auto")
        wall_s = time.perf_counter() - t0
        entry = {
            "baseline_kb": round(cp.baseline_bytes / 1024, 1),
            "dmo_kb": round(cp.peak_bytes / 1024, 1),
            "paper_kb": [paper_orig, paper_opt],
            "saving_pct": round(cp.saving_pct, 1),
            "compile_s": round(cp.compile_s, 3),
            "wall_s": round(wall_s, 3),
            "cache_hit": cp.cache_hit,
        }
        entry["winner"] = cp.winner
        if cp.order_stats:
            entry["fixed_dmo_kb"] = round(
                cp.order_stats["fixed_peak"] / 1024, 1)
            entry["order_search_s"] = round(cp.order_stats["wall_s"], 3)
            entry["order_changed"] = bool(cp.order_stats["order_changed"])
        bp = cp.legalised()
        if bp is not None:
            ws = bp.window_schedule()
            entry.update({
                "blocked_rows": bp.total_rows,
                "blocked_kb": round(bp.padded_peak_bytes / 1024, 1),
                "packed_peak_kb": round(bp.padded_peak_bytes / 1024, 1),
                "padding_overhead_pct": round(bp.padding_overhead_pct, 1),
                "legacy_blocked_kb": round(
                    (bp.legacy_padded_bytes or bp.padded_peak_bytes)
                    / 1024, 1),
                "packing": bp.packing,
                "window_rows": ws.max_window_rows,
                "window_pct": round(
                    100.0 * ws.max_window_rows / ws.total_rows, 1),
                "window_resident_bytes": ws.max_resident_bytes,
            })
            if X.executability(cp.graph) is None:
                from repro.core.exec.pallas_backend import PallasExecutor
                specs = PallasExecutor(layout="blocks",
                                       interpret=True).lower_blocks(bp)
                fused = [s for s in specs if s.kind == "fused"]
                entry.update({
                    "launches": len(specs),
                    "graph_ops": sum(1 for op in bp.order
                                     if op.kind != "reshape"),
                    "fused_chains": len(fused),
                    "fused_region_ops": sum(len(s.stages) for s in fused),
                    "fused_scratch_rows": max(
                        (s.scratch_rows for s in fused), default=0),
                })
        # memory-vs-batch trade curve: the rows a PlanServer routes on
        # (deterministic default compile kwargs — no search budget — so
        # the batched sweep stays cheap and cache-stable)
        from repro.core.pipeline import peak_vs_batch
        entry["peak_vs_batch"] = [
            {k: r[k] for k in ("batch", "peak_bytes", "per_image_bytes",
                               "peak_ratio_vs_b1")}
            for r in peak_vs_batch(build(), batches=(1, 2, 4, 8))]
        models[name] = entry

    exec_us = {}
    builds = {"f32": lambda: zoo.mobilenet_v1(0.25, 32, 4),
              "i8": lambda: zoo.mobilenet_v1(0.25, 32, 1)}
    backends = {
        "numpy": lambda: X.get_backend("numpy"),
        "pallas_flat": lambda: X.get_backend("pallas", layout="flat"),
        "pallas_blocks": lambda: X.get_backend("pallas", layout="blocks"),
        "pallas_stream": lambda: X.get_backend("pallas", mode="streaming",
                                               interpret=True),
    }
    for tier, build in builds.items():
        cp = compile_graph(build(), split="off")
        g = cp.graph
        weights = X.synth_weights(g)
        quant = X.calibrate(g, 0, weights) if X.needs_quant(g) else None
        inputs = (X.quant_inputs(g, quant) if quant is not None
                  else X.random_inputs(g))
        for bname, mk in backends.items():
            be = mk()
            be.execute(cp.plan, inputs, weights, quant=quant)  # warm jit
            t0 = time.perf_counter()
            n = 3
            for _ in range(n):
                be.execute(cp.plan, inputs, weights, quant=quant)
            exec_us[f"{tier}/{bname}"] = round(
                (time.perf_counter() - t0) / n * 1e6, 1)

    # serving demo: sustained inferences/sec on the 8-bit reduced flagship
    # through the deadline-batching PlanServer (batch variants 1..8)
    from repro.serve import throughput_demo
    serve = throughput_demo(zoo.mobilenet_v1(0.25, 32, 1), n_requests=512)

    return {
        "schema": "repro-dmo-bench-v4",
        "models": models,
        "exec_us_per_call": exec_us,
        "serve_throughput": serve,
        "sweep_rows": [[n, round(us, 1), d] for n, us, d in rows],
        "plan_cache": cache_info(),
    }


def main(argv=None) -> None:
    os.environ.setdefault("REPRO_DMO_DISK_CACHE", "1")
    ap = argparse.ArgumentParser(
        prog="benchmarks.run", description="DMO benchmark sweep")
    ap.add_argument("--json", nargs="?", const="BENCH_pr10.json",
                    default=None, metavar="PATH",
                    help="also write the structured benchmark artifact "
                         "(default path: BENCH_pr10.json)")
    ap.add_argument("--sweep", choices=("on", "off"), default="on",
                    help="run the full CSV sweep ('off' keeps --json cheap "
                         "on a warm plan cache)")
    args = ap.parse_args(argv)
    from repro.kernels.runtime import enable_compile_cache
    enable_compile_cache()

    rows = []
    if args.sweep == "on":
        from benchmarks import (arch_activation_plans, fig2_arena_report,
                                kernel_bench, op_removal, op_splitting,
                                roofline_report, table2_os_precision,
                                table3_memory_savings)
        mods = [
            ("table2 (O_s precision)", table2_os_precision),
            ("table3 (memory savings)", table3_memory_savings),
            ("fig2 (arena report)", fig2_arena_report),
            ("op splitting (§II.A)", op_splitting),
            ("op removal (§II.C)", op_removal),
            ("activation plans", arch_activation_plans),
            ("kernels", kernel_bench),
            ("roofline", roofline_report),
        ]
        for name, mod in mods:
            print(f"# --- {name}", file=sys.stderr, flush=True)
            mod.run(rows)
        print("name,us_per_call,derived")
        for n, us, d in rows:
            print(f"{n},{us:.1f},{d}")

    if args.json:
        payload = _json_payload(rows)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)

    from repro.core.pipeline import cache_info
    info = cache_info()
    print(f"# plan cache: mem {info['hits']} hit / {info['misses']} miss, "
          f"disk {info['disk_hits']} hit / {info['disk_misses']} miss "
          f"({info['size']} entries in memory, dir {info['disk_dir']})",
          file=sys.stderr)


if __name__ == "__main__":
    main()
