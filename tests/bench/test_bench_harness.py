"""CPU tests of the on-chip benchmark's own parts: the trace reduction, the
operation and byte counter, the peaks table and how cells are found."""
import json
import os
import re

import numpy as np
import pytest

from bench import check, loadgen, opcount, peaks, registry, tracefile
from bench.tracefile import Event, Trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]

KERNEL = ('%run.{} = s8[96,768]{{1,0}} custom-call(s8[96,768]{{1,0}} %a, '
          's8[3,3,3,8]{{3,2,1,0}} %w), custom_call_target="tpu_custom_call"')
COPY = "%copy.7 = s8[3,3,1,8]{3,2,1,0} copy(s8[3,3,1,8]{3,1,0,2} %b)"


def synthetic_trace():
    """Two annotated calls over [0, 100) ns of host time; device ops: two
    kernels in call 0 (overlapping each other), one copy and one kernel in
    call 1, and one kernel outside the calls that must not count."""
    device = [
        Event(KERNEL.format(1), 10, 10),     # [10, 20)
        Event(KERNEL.format(2), 15, 10),     # [15, 25): union [10, 25)
        Event(COPY, 60, 5),                  # [60, 65)
        Event(KERNEL.format(1), 70, 10),     # [70, 80)
        Event(KERNEL.format(1), 150, 10),    # outside the window
    ]
    python = [
        Event("bench_call", 0, 40), Event("bench_call", 50, 50),
        Event("shard_args", 2, 6),           # idle [0, 10) -> 6 ns of it
        Event("DevicePutWithSharding", 3, 2),  # nested: not counted
        Event("np.asarray(jax.Array)", 25, 20),  # idle [25, 60) -> 20 ns
    ]
    return Trace({"/device:TPU:0": device}, python)


def test_trace_reducer_busy_union_kernel_sum_and_idle_share():
    s = tracefile.summarise(synthetic_trace())
    assert s.calls == 2
    assert s.window_s == pytest.approx(100e-9)
    # busy: [10, 25) + [60, 65) + [70, 80) = 30 ns
    assert s.busy_s == pytest.approx(30e-9)
    # kernels inside the window: 10 + 10 + 10 ns, three events
    assert s.kernel_s == pytest.approx(30e-9)
    assert s.kernel_count == 3
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.7)
    idle = dict(s.idle_gaps)
    assert idle["shard_args"] == pytest.approx(6e-9)
    assert idle["np.asarray(jax.Array)"] == pytest.approx(20e-9)
    assert "DevicePutWithSharding" not in idle
    assert sum(idle.values()) == pytest.approx(70e-9)
    ops = dict(s.device_ops)
    assert ops["run.1 pallas(s8[3,3,3,8])"] == pytest.approx(20e-9)
    assert ops["copy.7 copy"] == pytest.approx(5e-9)


def test_trace_reducer_averages_over_chips():
    t = synthetic_trace()
    t.device_ops["/device:TPU:1"] = [Event(KERNEL.format(3), 0, 100)]
    s = tracefile.summarise(t)
    assert s.busy_s == pytest.approx((30e-9 + 100e-9) / 2)
    assert s.kernel_count == pytest.approx(2.0)


def test_trace_reducer_refuses_a_trace_without_calls():
    with pytest.raises(ValueError):
        tracefile.summarise(Trace({"/device:TPU:0": []}, []))


def test_union_merges_touching_and_nested_intervals():
    assert tracefile.union([(5, 7), (0, 2), (2, 3), (6, 6.5)]) == \
        [(0, 3), (5, 7)]


class _L:
    def __init__(self, kind, in_shape, out_shape, weight_shape=None):
        self.kind, self.in_shape, self.out_shape = kind, in_shape, out_shape
        self.weight_shape = weight_shape


@pytest.mark.parametrize("layer,db,batch,ops,nbytes", [
    # 3x3 stride-2 conv, 8x8x3 -> 4x4x8: 4*4*8 outputs x 27 MACs
    (_L("conv2d", (8, 8, 3), (4, 4, 8), (3, 3, 3, 8)), 1, 1,
     2 * 128 * 27, 192 + 128 + 216),
    # 3x3 depthwise, 4x4x8 -> 4x4x8: 128 outputs x 9 MACs, f32
    (_L("depthwise_conv2d", (4, 4, 8), (4, 4, 8), (3, 3, 8, 1)), 4, 1,
     2 * 128 * 9, 4 * (128 + 128 + 72)),
    # fully connected 256 -> 1000 at batch 8: weights read once per call
    (_L("fully_connected", (256,), (1000,), (256, 1000)), 1, 8,
     8 * 2 * 256000, 8 * 1256 + 256000),
    (_L("mean", (4, 4, 8), (8,)), 1, 2, 2 * 128, 2 * 136),
    (_L("softmax", (10,), (10,)), 4, 1, 0, 80),
])
def test_op_counter_hand_counts(layer, db, batch, ops, nbytes):
    assert opcount.layer_work(layer, db, batch) == (ops, nbytes)


def test_op_counter_ideal_time_takes_the_larger_bound():
    conv = _L("conv2d", (8, 8, 3), (4, 4, 8), (3, 3, 3, 8))
    ops, nbytes = opcount.layer_work(conv, 1)
    assert opcount.ideal_s_per_call([conv], 1, 1, 1.0, 1e9) == ops
    assert opcount.ideal_s_per_call([conv], 1, 1, 1e12, 1.0) == nbytes


def test_op_counter_refuses_an_unknown_kind():
    with pytest.raises(ValueError):
        opcount.layer_work(_L("attention", (4,), (4,)), 1)


def test_op_counter_takes_a_reference_modules_own_kinds():
    """A new architecture brings the work of its new layer kinds in its
    reference module; the counter's own formulas stay the default."""
    class Ref:
        @staticmethod
        def layer_work(layer, dtype_bytes, batch=1):
            if layer.kind != "add":
                return None
            n = 1
            for d in layer.out_shape:
                n *= d
            return n * batch, 3 * n * batch * dtype_bytes

    add = _L("add", (4, 4, 8), (4, 4, 8))
    conv = _L("conv2d", (8, 8, 3), (4, 4, 8), (3, 3, 3, 8))
    work = opcount.work_of(Ref)
    assert work(add, 4, 2) == (256, 3 * 128 * 2 * 4)
    assert work(conv, 1) == opcount.layer_work(conv, 1)
    assert opcount.ops_per_image([conv, add], 1, work) == \
        opcount.layer_work(conv, 1)[0] + 128
    assert opcount.ideal_s_per_call([add], 1, 1, 1.0, 1e12, work) == 128
    assert opcount.work_of(object()) is opcount.layer_work


@pytest.mark.parametrize("name,got,ref,value", [
    ("max_lsb", [3, -2, 0], [1, 0, 0], 2.0),
    ("mean_lsb", [[3, -2, 0], [0, 0, 0]], [[0, 0, 0], [0, 0, 0]], 5 / 3),
    ("max_log_gap", [0.5, 0.25], [0.5, 0.5], float(np.log(2))),
])
def test_check_numbers(name, got, ref, value):
    dtype = np.float32 if name == "max_log_gap" else np.int8
    got, ref = np.array(got, dtype), np.array(ref, dtype)
    assert check.gaps({name: 1.0}, got, ref)[name] == pytest.approx(value)
    assert check.gaps({name: 1.0}, got[..., :1], ref)[name] == float("inf")


def test_check_counts_answers_over_any_limit():
    ref = {0: np.zeros(4, np.int8)}
    answers = [(0, np.array([0, 0, 0, 1], np.int8)),
               (0, np.array([1, 1, 1, 1], np.int8)),
               (0, np.array([0, 0, 0, 9], np.int8))]
    v = check.compare({"max_lsb": 8, "mean_lsb": 0.5}, answers, ref)
    assert v["numbers"] == {"max_lsb": 9.0, "mean_lsb": 2.25}
    assert (v["compared"], v["failed"]) == (3, 2)


def test_peaks_lookup():
    row = peaks.peaks("TPU v5 lite")
    assert peaks.compute_peak(row, "int8") == 393e12
    assert peaks.compute_peak(row, "f32") == 197e12
    assert row["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", ""])
def test_peaks_lookup_refuses_unknown_device_kind(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)


@pytest.mark.parametrize("name", CELLS)
def test_harness_finds_a_cells_files_by_name(name):
    cell = registry.load_cell(name)
    w = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    assert cell.chips == w["chips"]
    assert os.path.exists(os.path.join(
        ROOT, "bench", "traffic", w["traffic"] + ".json"))
    assert cell.traffic["route"] in ("compiled", "streaming")
    ref = cell.reference()
    assert ref.layers(cell.config)
    assert callable(cell.model().graph)
    readers = registry.metric_readers(cell.per_layer)
    assert set(readers) == {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.config["check"]
    assert set(cell.config["check"]) <= set(check.NUMBERS)


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        registry.load_cell("no_such_cell")


def test_metric_readers_read_nothing_without_a_trace():
    readers = registry.metric_readers(MANIFEST["per_layer"])
    ctx = {"plan_s": 1.0, "batch": 1, "trace": None, "images_per_s": 0.0,
           "ops_per_image": 1, "peak_ops": 1.0, "ideal_s_per_call": 1.0}
    got = {n: r.read(ctx) for n, r in readers.items()}
    assert got.pop("plan_s") == 1.0
    assert all(v is None for v in got.values()), got


_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_manifest_names_units_and_keys():
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    names = [c["name"] for c in MANIFEST["configs"]] + CELLS + \
        [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    assert all(_NAME.match(n) for n in names)
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert _UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("name", ["single_stream_b1", "offline_b8"])
def test_input_pool_is_seeded_and_distinct(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        traffic = loadgen.validate(json.load(f))
    seed = 2 ** 31 + 12345
    a = loadgen.make_pool(traffic, (8, 8, 3), "int8", seed)
    b = loadgen.make_pool(traffic, (8, 8, 3), "int8", seed)
    assert all((x == y).all() for x, y in zip(a, b))
    imgs = loadgen.pool_images(a, traffic["batch"])
    assert len(imgs) == traffic["batch"] * traffic["pool_calls"]
    assert len({x.tobytes() for x in imgs}) == len(imgs)


def test_traced_window_annotates_the_traced_calls(monkeypatch, tmp_path):
    """The profiler plumbing on the CPU: the first ``TRACE_CALLS`` calls
    carry the benchmark's annotation in the trace, the rest run untraced,
    and the trace's directory is removed afterwards."""
    import jax.numpy as jnp
    import numpy as np

    from bench import run

    seen = {}

    def keep(trace):
        seen["trace"] = trace
        return "summary"

    monkeypatch.setattr(tracefile, "summarise", keep)
    monkeypatch.setattr(run, "TRACE_CALLS", 3)
    trace_dir = tmp_path / "trace"
    monkeypatch.setattr(run.tempfile, "mkdtemp",
                        lambda prefix: str(trace_dir.mkdir() or trace_dir))
    pool = [np.full((4,), i, np.float32) for i in range(2)]
    calls, span, summary = run.traced_window(
        lambda x: np.asarray(jnp.sum(x)), pool, 0.5)
    assert summary == "summary" and span > 0
    assert len(calls) > 3
    assert [k for k, _, _ in calls[:4]] == [0, 1, 0, 1]
    marks = [e for e in seen["trace"].python
             if e.name == tracefile.CALL_SPAN]
    assert len(marks) == 3
    assert not trace_dir.exists()
