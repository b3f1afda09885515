"""Batch-aware plans (PR 10): the batch axis through planner -> legaliser
-> kernels -> backends -> pipeline.

Four layers under test:

- planner: the scaled batch-1 bound ``peak(B) <= B * peak(1)`` (the
  ``_plan_scaled_batch1`` candidate guarantees it for every strategy
  winner, fused chains included), batched plans validating at every swept
  batch, and :func:`repro.core.pipeline.peak_vs_batch` row shape;
- exec: batched execution equals B stacked batch-1 runs — f32 bit-exact,
  int8 <= 1 LSB under one shared QuantSpec — on the reference AND the
  arena; pallas route parity (flat / blocks / streaming) at batch > 1,
  including forced fused band chains (the op-major stage expansion);
- pipeline: ``batch`` in the content-addressed plan-cache key, and
  ``compile_many`` fanning a graphs x batches grid across worker processes
  that share the disk plan-cache (atomic ``os.replace`` writes survive
  same-key races — satellite (a));
- property form: the peak bound + stacked equality as a hypothesis
  property over random band graphs (skips cleanly when hypothesis is
  absent; the parametrized grid above keeps the acceptance tested).
"""
from __future__ import annotations

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import exec as X
from repro.core import zoo
from repro.core.exec.numpy_backend import run_in_arena, run_reference
from repro.core.exec.ops import QuantSpec
from repro.core.graph import Graph
from repro.core.pipeline import (cache_clear, cache_info,
                                 compile as compile_graph, compile_many,
                                 peak_vs_batch)


def band_graph(h: int = 12, c: int = 4, db: int = 4, depth: int = 2,
               branch: bool = True) -> Graph:
    """Small conv tower: enough structure to split/fuse, cheap to execute."""
    g = Graph(f"bg_{h}_{c}_{db}_{depth}_{int(branch)}")
    x = g.tensor("x", (h, h, c), db, "input")
    cur = g.op("conv2d", [x], (h, h, c),
               dict(kernel=(3, 3), stride=(1, 1), padding="same"))
    for _ in range(depth):
        nxt = g.op("depthwise_conv2d", [cur], (h, h, c),
                   dict(kernel=(3, 3), stride=(1, 1), padding="same"))
        if branch:
            nxt = g.op("elementwise", [nxt, cur], (h, h, c), dict(fn="add"))
        cur = nxt
    p = g.op("pool", [cur], (h // 2, h // 2, c),
             dict(kernel=(2, 2), stride=(2, 2), padding="valid",
                  mode="max"))
    m = g.op("mean", [p], (c,), dict(axes=(0, 1)))
    g.op("fully_connected", [m], (8,), out_kind="output")
    g.validate()
    return g


_MODELS = {
    "mobilenet_v1_0.25_32_8bit": lambda: zoo.mobilenet_v1(0.25, 32, 1),
    "mobilenet_v2_0.35_32_f32": lambda: zoo.mobilenet_v2(0.35, 32, 4),
    "band_graph_f32": lambda: band_graph(),
    "band_graph_8bit": lambda: band_graph(db=1),
}


# ---------------------------------------------------------------------------
# planner: the scaled batch-1 peak bound + peak_vs_batch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(_MODELS))
def test_peak_bound_vs_batch1(name):
    """peak(B) <= B * peak(1) for every strategy winner (mobilenet_v2's
    fuse winner regressed this before the op-major fused stage expansion:
    atomic-chain liveness forced disjoint chain I/O)."""
    mk = _MODELS[name]
    peak1 = compile_graph(mk(), batch=1).peak_bytes
    for b in (2, 4, 8):
        cp = compile_graph(mk(), batch=b)
        assert cp.peak_bytes <= b * peak1, \
            f"{name} b={b}: {cp.peak_bytes} > {b}x{peak1}"
        assert cp.plan.peak_bytes == cp.peak_bytes
        cp.plan.validate()


def test_peak_vs_batch_rows():
    rows = peak_vs_batch(zoo.mobilenet_v1(0.25, 32, 1), batches=(1, 2, 4))
    assert [r["batch"] for r in rows] == [1, 2, 4]
    for r in rows:
        b = r["batch"]
        assert r["per_image_bytes"] == -(-r["peak_bytes"] // b)
        assert r["verified"]
        if b > 1:
            assert r["peak_ratio_vs_b1"] is not None
            assert r["peak_ratio_vs_b1"] <= 1.0 + 1e-9
        assert r["padded_peak_bytes"] is None \
            or r["padded_peak_bytes"] >= r["peak_bytes"]


# ---------------------------------------------------------------------------
# exec: batched == B stacked batch-1 runs (shared weights + QuantSpec)
# ---------------------------------------------------------------------------


def _remap_quant(q: QuantSpec, g1: Graph, gb: Graph) -> QuantSpec:
    """The batch-1 QuantSpec re-keyed for the positionally identical
    batched graph (activation params are by tensor name — shared as-is;
    weight tables are by ``id(op)``)."""
    assert len(g1.ops) == len(gb.ops)
    by_pos = dict(zip((id(o) for o in g1.ops), gb.ops))
    return QuantSpec(
        tensors=q.tensors,
        weight_scale={id(by_pos[k]): v for k, v in q.weight_scale.items()},
        weights_q={id(by_pos[k]): v for k, v in q.weights_q.items()})


def _check_stacked(mk, batch: int, split: str = "off") -> None:
    """Batched compile + numpy execution == ``batch`` stacked batch-1 runs
    (f32 bit-exact, int8 <= 1 LSB, one shared QuantSpec), reference AND
    planned arena."""
    cp1 = compile_graph(mk(), split=split)
    cpb = compile_graph(mk(), batch=batch, split=split)
    g1, gb = cp1.graph, cpb.graph
    assert [o.kind for o in g1.ops] == [o.kind for o in gb.ops]

    w1 = X.synth_weights(g1, 0)
    wb = {id(ob): w1[id(o1)] for o1, ob in zip(g1.ops, gb.ops)}
    q1 = qb = None
    if X.needs_quant(g1):
        q1 = X.calibrate(g1, 0, w1)
        qb = _remap_quant(q1, g1, gb)

    imgs = [(X.quant_inputs(g1, q1, seed=i) if q1 is not None
             else X.random_inputs(g1, seed=i)) for i in range(batch)]
    stacked = {k: np.stack([im[k] for im in imgs]) for k in imgs[0]}

    ref_b = run_reference(gb, stacked, weights=wb, quant=qb)
    for i, im in enumerate(imgs):
        ref_1 = run_reference(g1, im, weights=w1, quant=q1)
        for k, v in ref_1.items():
            got = ref_b[k][i]
            if v.dtype == np.int8:
                diff = np.abs(got.astype(np.int32) - v.astype(np.int32))
                assert diff.max(initial=0) <= 1, \
                    f"image {i} {k}: int8 diff {diff.max()}"
            else:
                assert np.array_equal(got, v), f"image {i} {k}"

    # the planned batched arena is bit-exact against its own reference
    arena = run_in_arena(gb, cpb.plan, stacked, weights=wb, quant=qb)
    for k, v in ref_b.items():
        assert np.array_equal(arena[k], v), f"arena {k}"


@pytest.mark.parametrize("name", ["band_graph_f32", "band_graph_8bit"])
@pytest.mark.parametrize("batch", [2, 4, 8])
def test_batched_equals_stacked_small(name, batch):
    _check_stacked(_MODELS[name], batch)


@pytest.mark.parametrize("name,batch", [
    ("mobilenet_v1_0.25_32_8bit", 4),
    ("mobilenet_v2_0.35_32_f32", 2),
])
def test_batched_equals_stacked_models(name, batch):
    _check_stacked(_MODELS[name], batch)


@given(h=st.sampled_from([8, 12, 16]), c=st.sampled_from([4, 8]),
       db=st.sampled_from([1, 4]), depth=st.integers(1, 3),
       branch=st.booleans(), batch=st.sampled_from([2, 4]))
@settings(max_examples=8, deadline=None)
def test_batching_property(h, c, db, depth, branch, batch):
    """Satellite (c): over random band graphs, the batched plan's byte
    peak stays <= B x the batch-1 peak AND the batched execution equals B
    stacked batch-1 runs."""
    mk = lambda: band_graph(h, c, db, depth, branch)   # noqa: E731
    peak1 = compile_graph(mk(), batch=1).peak_bytes
    cp = compile_graph(mk(), batch=batch)
    assert cp.peak_bytes <= batch * peak1
    cp.plan.validate()
    _check_stacked(mk, batch)


# ---------------------------------------------------------------------------
# kernels/backends: pallas route parity at batch > 1
# ---------------------------------------------------------------------------


_ROUTES = {
    "flat": dict(layout="flat"),
    "blocks": dict(layout="blocks"),
    "stream": dict(mode="streaming", interpret=True),
}


@pytest.mark.parametrize("route", list(_ROUTES))
def test_batched_pallas_parity_model(route):
    """Real-model batched parity on the 8-bit reduced flagship under the
    full strategy competition (its winner fuses band chains — this is the
    path that clobbered image >= 1 inputs before the op-major rework)."""
    cp = compile_graph(zoo.mobilenet_v1(0.25, 32, 1), batch=2)
    X.cross_check(cp, backends=(
        "numpy", X.get_backend("pallas", **_ROUTES[route])))


@pytest.mark.parametrize("route", ["flat", "stream"])
def test_batched_fused_forced_parity(route):
    """Forced fused band chains at batch > 1 (independent of which
    strategy wins the competition: split bands, then chain them by hand —
    small graphs never split, so this runs on the reduced flagship)."""
    from repro.core.planner import plan_dmo
    from repro.core.splitting import fuse_chains
    cp = compile_graph(zoo.mobilenet_v1(0.25, 32, 1), batch=2,
                       split="on", fuse="off", verify="constraints")
    gf = fuse_chains(cp.graph)
    assert gf is not None
    assert sum(1 for op in gf.ops if "fuse_chain" in op.params) > 0
    plan = plan_dmo(gf)
    plan.validate()
    X.cross_check(plan, backends=(
        "numpy", X.get_backend("pallas", **_ROUTES[route])))


@pytest.mark.parametrize("batch", [4])
def test_batched_pallas_parity_small_f32(batch):
    cp = compile_graph(band_graph(), batch=batch)
    for route in _ROUTES:
        X.cross_check(cp, backends=(
            "numpy", X.get_backend("pallas", **_ROUTES[route])))


# ---------------------------------------------------------------------------
# pipeline: batch in the cache key; compile_many; disk-store races
# ---------------------------------------------------------------------------


def test_batch_in_cache_key():
    cache_clear()
    c1 = compile_graph(band_graph(), batch=1)
    c2 = compile_graph(band_graph(), batch=2)
    assert not c2.cache_hit          # batch=2 is a different key
    assert c2.key != c1.key
    c2b = compile_graph(band_graph(), batch=2)
    assert c2b.cache_hit
    assert c2b.peak_bytes == c2.peak_bytes


def test_compile_many_shares_disk_cache(tmp_path, monkeypatch):
    """Two spawned workers over a graphs x batches grid; a second run after
    clearing the in-memory tier must be served entirely from the disk
    entries the first run's workers wrote."""
    monkeypatch.setenv("REPRO_DMO_CACHE_DIR", str(tmp_path))
    gs = [band_graph(), band_graph(db=1)]
    res1 = compile_many(gs, batches=(1, 2), workers=2)
    assert len(res1) == 4
    cache_clear()
    res2 = compile_many(gs, batches=(1, 2), workers=2)
    assert sum(r["disk_hits"] for r in res2) == len(res2), res2
    for a, b in zip(res1, res2):
        assert (a["graph"], a["batch"], a["peak_bytes"]) \
            == (b["graph"], b["batch"], b["peak_bytes"])


def test_compile_many_workers_pinned_to_cpu(monkeypatch):
    """compile_many workers only plan: each starts on the CPU platform
    whatever the parent's, so a parent holding the chip never starves
    them."""
    import multiprocessing as mp
    import os

    from repro.core.pipeline import _pin_worker_to_cpu
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with mp.get_context("spawn").Pool(
            1, initializer=_pin_worker_to_cpu) as pool:
        assert pool.apply(os.getenv, ("JAX_PLATFORMS",)) == "cpu"


def test_disk_store_same_key_race(tmp_path, monkeypatch):
    """Satellite (a): concurrent same-key writers race benignly through
    the tmp-file + atomic-replace protocol — two workers compiling the
    SAME (graph, batch) job leave one loadable entry behind."""
    monkeypatch.setenv("REPRO_DMO_CACHE_DIR", str(tmp_path))
    res = compile_many([band_graph(), band_graph()], batches=(1,),
                       workers=2)
    assert res[0]["peak_bytes"] == res[1]["peak_bytes"]
    assert not list(tmp_path.glob("*.tmp.*"))    # no orphaned temp files
    cache_clear()
    cp = compile_graph(band_graph(), batch=1, disk_cache=True)
    assert cache_info()["disk_hits"] >= 1
    assert cp.peak_bytes == res[0]["peak_bytes"]


def test_disk_store_corrupt_entry_degrades(tmp_path, monkeypatch):
    """An unreadable persisted entry is a cold miss, never a crash."""
    monkeypatch.setenv("REPRO_DMO_CACHE_DIR", str(tmp_path))
    cache_clear()
    compile_graph(band_graph(), batch=2, disk_cache=True)
    entries = list(tmp_path.glob("*.pkl"))
    assert entries
    for p in entries:
        p.write_bytes(b"not a pickle")
    cache_clear()
    cp = compile_graph(band_graph(), batch=2, disk_cache=True)
    assert not cp.cache_hit
    assert cache_info()["disk_misses"] >= 1
