"""The executor's own instrumentation: the ``dmo.*`` phase spans that
``PallasExecutor.execute`` writes into a profiler trace, and the counters
of ``PallasExecutor.stats()``."""
import os

import numpy as np
import pytest

from repro.core import pipeline, zoo
from repro.core.exec.pallas_backend import PallasExecutor

PHASES = ("dmo.resolve", "dmo.legalise", "dmo.seed_arena", "dmo.upload",
          "dmo.launch", "dmo.fetch", "dmo.gather")
CALLER = "caller"


def _filter_bytes(op, dtype_bytes: int) -> int:
    """Bytes of one weighted op's filter, from its shapes."""
    if op.kind == "fully_connected":
        return op.inputs[0].shape[-1] * op.output.shape[-1] * dtype_bytes
    kh, kw = op.params["kernel"]
    ic = op.inputs[0].shape[-1]
    oc = (op.output.shape[-1] if op.kind == "conv2d"
          else op.params.get("multiplier", 1))
    return kh * kw * ic * oc * dtype_bytes


def _filter_bytes_of(cp) -> int:
    """The bytes of a plan's filters, from its shapes: each distinct filter
    once (split bands share their source layer's)."""
    weighted = ("conv2d", "depthwise_conv2d", "fully_connected")
    db = cp.graph.tensors[0].dtype_bytes
    filters = {op.params.get("split_src", op.name): _filter_bytes(op, db)
               for op in cp.plan.order if op.kind in weighted}
    return sum(filters.values())


def _arena_bytes(cp) -> int:
    bp = cp.legalised()
    return bp.total_rows * bp.row_bytes


def _h2d_per_call(cp) -> int:
    """The bytes a call with parameters new to the executor uploads: each
    distinct filter once, plus the typed arena."""
    return _filter_bytes_of(cp) + _arena_bytes(cp)


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Two interpret-mode calls of a fresh executor, each inside a caller's
    annotation, recorded by the profiler; returns the executor, the plan
    and the calling thread's events as (name, start, end, stats)."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    cp = pipeline.compile(zoo.mobilenet_v1(0.25, 32, 1), cache=False)
    be = PallasExecutor(layout="blocks", interpret=True)
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        results = []
        for _ in range(2):
            with TraceAnnotation(CALLER):
                results.append(be.execute(cp))
    finally:
        jax.profiler.stop_trace()
    paths = [os.path.join(d, f) for d, _, fs in os.walk(out)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(paths) == 1
    events = None
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    {k: v for k, v in e.stats}) for e in line.events]
            if any(e[0] == CALLER for e in evs):
                events = evs
    assert events is not None, "no host line holds the caller's spans"
    return be, cp, events, results


def _phases_of(events):
    """Per caller span, the dmo.* events inside it, in start order."""
    calls = sorted(e for e in events if e[0] == CALLER)
    return [sorted((e for e in events if e[0].startswith("dmo.")
                    and s <= e[1] and e[2] <= t), key=lambda e: e[1])
            for _, s, t, _ in calls]


def test_execute_emits_the_seven_phase_spans_in_order(profiled):
    _, _, events, _ = profiled
    per_call = _phases_of(events)
    assert len(per_call) == 2
    for n, spans in enumerate(per_call):
        assert tuple(e[0] for e in spans) == PHASES
        for a, b in zip(spans, spans[1:]):
            assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
        assert all(e[3].get("call") == n for e in spans)
    # no phase span outside a caller: the executor adds no outer span
    assert sum(len(s) for s in per_call) == sum(
        1 for e in events if e[0].startswith("dmo."))


def test_upload_and_fetch_spans_carry_their_bytes(profiled):
    """Call 0 uploads the filters and the arena; call 1, with the same
    parameters, finds the filters resident and uploads the arena alone."""
    be, cp, events, _ = profiled
    uploaded = [_h2d_per_call(cp), _arena_bytes(cp)]
    for n, spans in enumerate(_phases_of(events)):
        by = {e[0]: e[3] for e in spans}
        assert by["dmo.upload"]["bytes"] == uploaded[n]
        assert by["dmo.fetch"]["bytes"] == _arena_bytes(cp)


def test_stats_count_calls_bytes_and_programs(profiled):
    be, cp, _, results = profiled
    st = be.stats()
    bp = cp.legalised()
    n_filters = len({op.params.get("split_src", op.name)
                     for op in cp.plan.order
                     if op.kind in ("conv2d", "depthwise_conv2d",
                                    "fully_connected")})
    assert st["calls"] == 2 and st["images"] == 2
    assert st["h2d_bytes"] == _filter_bytes_of(cp) + 2 * _arena_bytes(cp)
    assert st["d2h_bytes"] == 2 * bp.total_rows * bp.row_bytes
    assert st["uploads"] == n_filters + 2
    assert (st["weight_misses"], st["weight_hits"]) == (1, 1)
    assert (st["lowering_misses"], st["lowering_hits"]) == (1, 1)
    assert st["programs_built"] == 1 and st["first_call_s"] > 0
    for k in results[0]:
        np.testing.assert_array_equal(results[0][k], results[1][k])


def test_stats_count_launches_by_kind(profiled):
    """``launches.<kind>`` counts each call's kernels by op kind, over all
    calls: 30 launches a call at 32 px (14 conv2d, 13 depthwise, mean,
    fully connected and softmax), twice."""
    be, _, _, _ = profiled
    launches = {k: v for k, v in be.stats().items()
                if k.startswith("launches.")}
    assert launches == {"launches.conv2d": 28,
                        "launches.depthwise_conv2d": 26,
                        "launches.mean": 2,
                        "launches.fully_connected": 2,
                        "launches.softmax": 2}


def test_stats_count_the_rows_each_launch_stages(profiled):
    """``stage_bytes`` counts, per call, each launch's DMA-aligned input
    and output rows once (the union of their 8-row groups) on the way in
    and the output's groups on the way back. A diagonal overlap shares
    rows between a launch's input and output, and the total stays far
    below the whole arena in and out per launch."""
    be, cp, _, _ = profiled
    bp = cp.legalised()
    specs = PallasExecutor(layout="blocks", interpret=True).lower_blocks(bp)

    def groups(off, rows):
        return set(range(off // 8, -(-(off + rows) // 8)))

    per_call, overlaps = 0, 0
    for s in specs:
        ins = set().union(*(groups(o, r) for o, (r, _)
                            in zip(s.in_off, s.in_rows)))
        out = groups(s.out_off, s.out_rows[0])
        overlaps += bool(ins & out)
        per_call += 8 * (len(ins | out) + len(out)) * bp.row_bytes
    assert overlaps
    assert be.stats()["stage_bytes"] == 2 * per_call
    assert per_call < 2 * len(specs) * bp.total_rows * bp.row_bytes


def test_every_kernel_is_named_by_its_kind_and_op():
    """Each ``pallas_call`` carries ``dmo_<kind>_<op>`` as its name, so a
    device trace read by hand tells the kernels apart."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import arena_ops
    cp = pipeline.compile(zoo.mobilenet_v1(0.25, 32, 4), cache=False)
    bp = cp.legalised()
    specs = PallasExecutor(interpret=True).lower_blocks(bp)
    names = [arena_ops.kernel_name(s) for s in specs]
    assert names[:3] == ["dmo_conv2d_conv1", "dmo_depthwise_conv2d_dw1",
                         "dmo_conv2d_pw1"]
    assert names[-1] == "dmo_softmax_prob"
    assert all(n.startswith(f"dmo_{s.kind}_") for n, s in zip(names, specs))
    arena = jnp.zeros((bp.total_rows, bp.arena_rowlen), jnp.float32)
    w = jnp.zeros((3, 3, 3, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a: arena_ops.apply_op(
        a, specs[0], (w,), interpret=True))(arena)
    assert "name=dmo_conv2d_conv1" in str(jaxpr)


def test_stats_count_images_and_shared_filters_of_a_batch():
    """At batch 2 every filter is passed once per image but uploaded once,
    and a call completes two images. (Float, where the split bands of one
    layer share one filter array; the int8 calibration quantises each
    band's copy apart, and the executor uploads each array it is given.)"""
    cp = pipeline.compile(zoo.mobilenet_v1(0.25, 32, 4), batch=2,
                          cache=False)
    be = PallasExecutor(layout="blocks", interpret=True)
    be.execute(cp)
    st = be.stats()
    assert (st["calls"], st["images"]) == (1, 2)
    assert st["h2d_bytes"] == _h2d_per_call(cp)
    assert st["programs_built"] == 1


def test_stats_start_at_zero():
    st = PallasExecutor(interpret=True).stats()
    assert set(st) == {"calls", "images", "h2d_bytes", "d2h_bytes",
                       "uploads", "weight_hits", "weight_misses",
                       "lowering_hits", "lowering_misses",
                       "programs_built", "first_call_s", "stage_bytes"}
    assert not any(st.values())
