"""Filters kept resident on the device across ``PallasExecutor.execute``
calls: the first call with a plan, weights dict and ``QuantSpec`` uploads
the distinct filters, later calls with the same objects upload the arena
alone, and new objects upload again (interpret mode, host CPU)."""
import numpy as np
import pytest

from repro.core import pipeline, zoo
from repro.core.exec import ops as X
from repro.core.exec.pallas_backend import RESIDENT_PARAM_SETS, PallasExecutor


@pytest.fixture(scope="module")
def fp32():
    return pipeline.compile(zoo.mobilenet_v1(0.25, 32, 4), cache=False)


@pytest.fixture(scope="module")
def int8():
    return pipeline.compile(zoo.mobilenet_v1(0.25, 32, 1), cache=False)


def _arena_bytes(cp) -> int:
    bp = cp.legalised()
    return bp.total_rows * bp.row_bytes


def _filter_bytes(weights) -> int:
    """Bytes of a float weights dict's distinct filter arrays."""
    return sum({id(w["filter"]): w["filter"].nbytes
                for w in weights.values() if "filter" in w}.values())


def _run(be, cp, inputs, weights, quant=None):
    """One call's outputs and the change it made to the upload counters."""
    before = be.stats()
    out = be.execute(cp, inputs, weights, quant=quant)
    after = be.stats()
    return out, {k: after[k] - before[k]
                 for k in ("h2d_bytes", "uploads", "weight_hits",
                           "weight_misses")}


def _assert_same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_same_parameters_hit_and_upload_only_the_arena(fp32):
    be = PallasExecutor(layout="blocks", interpret=True)
    w = X.synth_weights(fp32.graph, 0)
    x = X.random_inputs(fp32.graph, 0)
    out0, d0 = _run(be, fp32, x, w)
    out1, d1 = _run(be, fp32, x, w)
    _assert_same(out0, out1)
    assert (d0["weight_misses"], d0["weight_hits"]) == (1, 0)
    assert d0["h2d_bytes"] == _filter_bytes(w) + _arena_bytes(fp32)
    assert (d1["weight_misses"], d1["weight_hits"]) == (0, 1)
    assert d1["h2d_bytes"] == _arena_bytes(fp32)
    assert d1["uploads"] == 1


def test_a_new_weights_dict_misses_and_gives_its_own_outputs(fp32):
    be = PallasExecutor(layout="blocks", interpret=True)
    w0, w1 = X.synth_weights(fp32.graph, 0), X.synth_weights(fp32.graph, 1)
    x = X.random_inputs(fp32.graph, 0)
    out0, _ = _run(be, fp32, x, w0)
    out1, d1 = _run(be, fp32, x, w1)
    assert (d1["weight_misses"], d1["weight_hits"]) == (1, 0)
    assert d1["h2d_bytes"] == _filter_bytes(w1) + _arena_bytes(fp32)
    # w1's answers, as a fresh executor gives them, and not w0's
    fresh = PallasExecutor(layout="blocks", interpret=True)
    _assert_same(out1, fresh.execute(fp32, x, w1))
    assert any(not np.array_equal(out0[k], out1[k]) for k in out0)


def test_a_new_quantspec_misses(int8):
    be = PallasExecutor(layout="blocks", interpret=True)
    g = int8.graph
    w = X.synth_weights(g, 0)
    q0, q1 = X.calibrate(g, 0, w), X.calibrate(g, 0, w)
    x = X.quant_inputs(g, q0, 0)
    out0, d0 = _run(be, int8, x, w, q0)
    out1, d1 = _run(be, int8, x, w, q1)
    _, d2 = _run(be, int8, x, w, q1)
    assert (d0["weight_misses"], d1["weight_misses"]) == (1, 1)
    assert d1["h2d_bytes"] == d0["h2d_bytes"] > _arena_bytes(int8)
    assert (d2["weight_hits"], d2["h2d_bytes"]) == (1, _arena_bytes(int8))
    _assert_same(out0, out1)


def test_the_oldest_parameter_set_is_evicted_past_the_bound(fp32):
    be = PallasExecutor(layout="blocks", interpret=True)
    x = X.random_inputs(fp32.graph, 0)
    sets = [X.synth_weights(fp32.graph, s)
            for s in range(RESIDENT_PARAM_SETS + 1)]
    outs = [_run(be, fp32, x, w)[0] for w in sets]
    assert be.stats()["weight_misses"] == RESIDENT_PARAM_SETS + 1
    # the newest sets are still resident; the first was freed
    _, newest = _run(be, fp32, x, sets[-1])
    assert newest["weight_hits"] == 1
    again, first = _run(be, fp32, x, sets[0])
    assert (first["weight_misses"], first["weight_hits"]) == (1, 0)
    assert first["h2d_bytes"] == _filter_bytes(sets[0]) + _arena_bytes(fp32)
    _assert_same(outs[0], again)


def test_a_batch_uploads_each_shared_filter_once_then_only_the_arena():
    """At batch 2 each filter is passed once per image; the first call
    uploads each distinct filter once, the second none."""
    cp = pipeline.compile(zoo.mobilenet_v1(0.25, 32, 4), batch=2,
                          cache=False)
    be = PallasExecutor(layout="blocks", interpret=True)
    w = X.synth_weights(cp.graph, 0)
    x = X.random_inputs(cp.graph, 0)
    n_filters = len({id(v["filter"]) for v in w.values() if "filter" in v})
    _, d0 = _run(be, cp, x, w)
    _, d1 = _run(be, cp, x, w)
    assert d0["uploads"] == n_filters + 1
    assert d0["h2d_bytes"] == _filter_bytes(w) + _arena_bytes(cp)
    assert (d1["uploads"], d1["h2d_bytes"]) == (1, _arena_bytes(cp))
    assert be.stats()["images"] == 4
