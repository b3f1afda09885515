"""Row-blocked arena layouts: the block-granular plan legaliser
(`legalise_for_blocks`), its tiling invariants over the zoo, row-blocked
Pallas execution parity against the flat program and the numpy backend
(f32 + int8), unsafe-overlap negatives at row granularity, and the
compiled-mode / interpret-mode plumbing."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import exec as X
from repro.core import planner as P
from repro.core import zoo
from repro.core.arena import run_reference
from repro.core.graph import Graph
from repro.core.planner import (BlockLayout, BlockPlan, TPU_TILES,
                                legalise_for_blocks, plan_dmo, plan_greedy_size)

pytestmark = pytest.mark.filterwarnings("ignore:.*donated.*")


def small_conv_graph(dtype_bytes=4):
    g = Graph("smallconv")
    x = g.tensor("x", (8, 8, 4), dtype_bytes, "input")
    h = g.op("conv2d", [x], (8, 8, 6),
             dict(kernel=(3, 3), stride=(1, 1), padding="same"))
    h = g.op("pool", [h], (4, 4, 6),
             dict(kernel=(2, 2), stride=(2, 2), padding="valid", mode="max"))
    g.op("elementwise", [h], (4, 4, 6), dict(fn="relu"), out_kind="output")
    g.validate()
    return g


def _assert_block_invariants(bp: BlockPlan):
    sub, lanes = bp.tiling
    assert bp.arena_rowlen % lanes == 0       # lane-tiled arena row
    assert bp.total_rows % sub == 0           # sublane-tiled arena height
    for t, lay in bp.layouts.items():
        assert isinstance(lay, BlockLayout)
        assert lay.row_offset % bp.row_align == 0, \
            f"{lay.name}: row offset {lay.row_offset} not " \
            f"{bp.row_align}-aligned"
        assert lay.row_offset + lay.rows <= bp.total_rows
        assert 0 < lay.rowlen <= bp.arena_rowlen
        assert lay.rows * lay.rowlen >= lay.elems
        # byte plan view stays consistent with the block view
        assert bp.offsets[t] == lay.row_offset * bp.row_bytes
    assert bp.padded_peak_bytes >= (bp.source or bp).peak_bytes
    bp.validate()  # byte-level + row-granular no-clobber


# ---------------------------------------------------------------------------
# The legaliser over the whole zoo (acceptance: every f32 and int8 zoo model
# legalises to a row-blocked layout)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(zoo.TABLE3_MODELS))
def test_zoo_legalises_row_blocked(name):
    g = zoo.TABLE3_MODELS[name][0]()
    # one DMO strategy keeps the sweep affordable on the big connected
    # graphs; the flagship tests below use the full plan_dmo
    plan = plan_greedy_size(g, overlap_fn=P._default_overlap("algorithmic"))
    bp = legalise_for_blocks(plan)
    sub, lanes = TPU_TILES[g.tensors[0].dtype_bytes]
    assert bp.tiling == (sub, lanes)
    _assert_block_invariants(bp)
    assert bp.strategy.endswith("+blocks")
    # the padding-overhead line the report states
    assert f"+{bp.padding_overhead_pct:.1f}%" in bp.report()


@pytest.mark.parametrize("name", zoo.TABLE3_8BIT_MODELS)
def test_flagship_8bit_rows_legalise_with_bounded_padding(name):
    """Both flagship Table III rows legalise under the int8 (32, 128) tile
    with padding overhead within the bound table3_memory_savings states."""
    from benchmarks.table3_memory_savings import padding_bound_pct
    g = zoo.TABLE3_MODELS[name][0]()
    bp = legalise_for_blocks(plan_dmo(g))
    assert bp.tiling == TPU_TILES[1]
    _assert_block_invariants(bp)
    assert bp.padding_overhead_pct <= padding_bound_pct(name), \
        f"{name}: +{bp.padding_overhead_pct:.1f}% over stated bound"


@settings(max_examples=15, deadline=None)
@given(st.integers(5, 16), st.integers(5, 16), st.sampled_from([1, 2, 4]),
       st.sampled_from([3, 5]), st.integers(1, 2),
       st.sampled_from(["same", "valid"]), st.sampled_from([1, 4]))
def test_legalise_property_conv_chain(ih, iw, c, k, stride, padding, db):
    """Hypothesis-style: random small conv chains legalise with tile-aligned
    offsets and a row-granular validate pass, in both dtype tiers."""
    from repro.core.graph import conv_out_dim
    if ih + (2 if padding == "same" else 0) < k:
        return
    oh = conv_out_dim(ih, k, stride, padding)
    ow = conv_out_dim(iw, k, stride, padding)
    if oh < 1 or ow < 1:
        return
    g = Graph("prop")
    x = g.tensor("x", (ih, iw, c), db, "input")
    h = g.op("conv2d", [x], (oh, ow, c + 2),
             dict(kernel=(k, k), stride=(stride, stride), padding=padding))
    g.op("elementwise", [h], (oh, ow, c + 2), dict(fn="relu"),
         out_kind="output")
    g.validate()
    bp = legalise_for_blocks(plan_dmo(g))
    _assert_block_invariants(bp)


def test_legalise_rejects_mixed_dtype():
    g = Graph("mixed")
    a = g.tensor("a", (4, 4), 1, "input")
    b = g.tensor("b", (4, 4), 4, "input")
    g.op("elementwise", [a], (4, 4), dict(fn="relu"), out_kind="output")
    g.op("elementwise", [b], (4, 4), dict(fn="relu"), name="e2",
         out_kind="output")
    g.validate()
    with pytest.raises(ValueError, match="mixed-dtype"):
        legalise_for_blocks(plan_dmo(g))
    # and the pallas backend refuses blocks explicitly but auto-falls back
    with pytest.raises(ValueError, match="mixed-dtype"):
        X.get_backend("pallas", layout="blocks").execute(plan_dmo(g))
    X.cross_check(plan_dmo(g))  # auto layout falls back to the flat program


def test_legalise_rejects_aggregated_views():
    from repro.core.removal import remove_concats
    g = Graph("cat")
    x = g.tensor("x", (4, 4, 2), 4, "input")
    a = g.op("conv2d", [x], (4, 4, 2),
             dict(kernel=(1, 1), stride=(1, 1), padding="same"), name="a")
    b = g.op("conv2d", [x], (4, 4, 2),
             dict(kernel=(1, 1), stride=(1, 1), padding="same"), name="b")
    c = g.op("concat", [a, b], (4, 4, 4), dict(axis=-1))
    g.op("elementwise", [c], (4, 4, 4), dict(fn="relu"), out_kind="output")
    g.validate()
    rg = remove_concats(g)
    with pytest.raises(ValueError, match="views"):
        legalise_for_blocks(plan_dmo(rg))


def test_legalise_refuses_unsafe_source_plan():
    """The legaliser re-places tensors, so it must never silently repair a
    clobbering byte plan — verify_plan's negative contract survives the
    row-blocked path on both backends (see test_executors negatives)."""
    g = Graph("bad")
    x = g.tensor("x", (8, 8, 4), 4, "input")
    y = g.op("conv2d", [x], (8, 8, 8),
             dict(kernel=(3, 3), stride=(1, 1), padding="same"),
             out_kind="output")
    bad = P.Plan(g, list(g.ops), {x.storage(): 0, y.storage(): 0}, {}, "bogus")
    with pytest.raises(AssertionError):
        legalise_for_blocks(bad)


def test_row_granular_validate_catches_shared_live_rows():
    """A hand-built BlockPlan whose tensors share live rows (beyond any
    recorded O_s) fails the row-granular validate and mis-executes on the
    row-blocked program — the §I verification at block granularity."""
    g = small_conv_graph()
    good = legalise_for_blocks(plan_dmo(g))
    # clone the good block plan but collapse every tensor onto row 0
    layouts = {t: BlockLayout(l.name, l.shape, l.dtype_bytes, 0, l.rows,
                              l.rowlen)
               for t, l in good.layouts.items()}
    bad = BlockPlan(good.graph, list(good.order),
                    {t: 0 for t in good.offsets}, {}, "bogus+blocks",
                    source=good.source, tiling=good.tiling,
                    arena_rowlen=good.arena_rowlen,
                    total_rows=good.total_rows, layouts=layouts)
    with pytest.raises(AssertionError):
        bad.validate()
    # executing the clobbering block layout yields wrong outputs
    inputs = X.random_inputs(g)
    weights = X.synth_weights(g)
    ref = run_reference(g, inputs, bad.order, weights=weights)
    got = X.get_backend("pallas").execute(bad, inputs, weights)
    with pytest.raises(AssertionError):
        X.compare_outputs(ref, got, exact=False, label="bogus blocks")
    # ... and the numpy backend clobbers at the same (byte-view) offsets
    got_np = X.get_backend("numpy").execute(bad, inputs, weights)
    with pytest.raises(AssertionError):
        X.compare_outputs(ref, got_np, exact=True, label="bogus blocks np")


def test_row_validate_checks_block_footprints_not_padded_bytes():
    """Image-layout tensors reserve H arena rows but pack fewer *bytes*
    than those rows hold, so a byte-granularity check under-counts them: a
    layout that is byte-disjoint yet interleaves reserved rows must still
    fail the block-footprint validate (the regression behind the
    ``_validate_rows`` override)."""
    g = Graph("rowclash")
    x = g.tensor("x", (8, 8, 4), 4, "input")
    g.op("conv2d", [x], (8, 8, 8),
         dict(kernel=(3, 3), stride=(1, 1), padding="same"),
         out_kind="output")
    good = legalise_for_blocks(plan_dmo(g))
    rb = good.row_bytes
    y = g.ops[0].output.storage()
    # y at rows [0, 8); x at rows [4, 12): rows 4..7 are shared while the
    # *byte* extents are disjoint (y's 2048 data bytes end exactly at x's
    # 4*rb = 2048 byte offset) — only the row-footprint walk can see it
    lay = dict(good.layouts)
    lay[y] = BlockLayout(y.name, y.shape, 4, 0, lay[y].rows, lay[y].rowlen)
    lay[x.storage()] = BlockLayout(x.name, x.shape, 4, 4,
                                   lay[x.storage()].rows,
                                   lay[x.storage()].rowlen)
    bad = BlockPlan(g, list(good.order), {y: 0, x.storage(): 4 * rb}, {},
                    "bogus+blocks", source=good.source, tiling=good.tiling,
                    arena_rowlen=good.arena_rowlen,
                    total_rows=good.total_rows + 8, layouts=lay)
    assert y.nbytes <= 4 * rb  # byte extents genuinely disjoint
    P.Plan.validate(bad)       # the byte-granular check cannot see it
    with pytest.raises(AssertionError, match="rows"):
        bad.validate()


# ---------------------------------------------------------------------------
# Row-blocked execution parity: blocked pallas vs flat pallas vs numpy
# ---------------------------------------------------------------------------

_PARITY_SWEEP = {
    "mobilenet_v1_0.25_32_f32": lambda: zoo.mobilenet_v1(0.25, 32, 4),
    "mobilenet_v1_0.25_32_8bit": lambda: zoo.mobilenet_v1(0.25, 32, 1),
    "mobilenet_v2_0.35_32_8bit": lambda: zoo.mobilenet_v2(0.35, 32, 1),
}


@pytest.mark.parametrize("name", list(_PARITY_SWEEP))
def test_row_blocked_parity_reduced_zoo(name):
    """Blocked program == flat program == numpy backend on reduced-res zoo
    builds, both dtype tiers (bit-exact numpy reference; <= 1 LSB int8 /
    fp32 tol on pallas)."""
    g = _PARITY_SWEEP[name]()
    plan = plan_dmo(g)
    assert plan.overlaps, "expected O_s overlaps to stress the layout"
    weights = X.synth_weights(g)
    quant = X.calibrate(g, 0, weights) if X.needs_quant(g) else None
    inputs = (X.quant_inputs(g, quant) if quant is not None
              else X.random_inputs(g))
    ref = run_reference(g, inputs, plan.order, weights=weights, quant=quant)
    blocked = X.get_backend("pallas", layout="blocks").execute(
        plan, inputs, weights, quant=quant)
    flat = X.get_backend("pallas", layout="flat").execute(
        plan, inputs, weights, quant=quant)
    numpy_ = X.get_backend("numpy").execute(plan, inputs, weights,
                                            quant=quant)
    X.compare_outputs(ref, numpy_, exact=True, label="numpy vs reference")
    X.compare_outputs(numpy_, flat, exact=False, label="flat vs numpy")
    X.compare_outputs(numpy_, blocked, exact=False, label="blocked vs numpy")
    X.compare_outputs(flat, blocked, exact=False, label="blocked vs flat")


@pytest.mark.parametrize("name", zoo.TABLE3_8BIT_MODELS)
def test_flagship_8bit_rows_blocked_parity(name):
    """Acceptance: both flagship 8-bit Table III rows (full resolution)
    execute the row-blocked Pallas program (interpret mode on CPU) and match
    the numpy backend to <= 1 LSB."""
    g = zoo.TABLE3_MODELS[name][0]()
    plan = plan_dmo(g)
    weights = X.synth_weights(g)
    quant = X.calibrate(g, 0, weights)
    inputs = X.quant_inputs(g, quant)
    got_np = X.get_backend("numpy").execute(plan, inputs, weights,
                                            quant=quant)
    got_blk = X.get_backend("pallas", layout="blocks").execute(
        plan, inputs, weights, quant=quant)
    for k in got_np:
        assert got_np[k].dtype == np.int8
        np.testing.assert_allclose(got_blk[k].astype(np.int32),
                                   got_np[k].astype(np.int32),
                                   rtol=0, atol=X.INT8_ATOL, err_msg=k)


def test_blocked_specs_lowering():
    """lower_blocks emits row-granular specs: row offsets + (rows, used)
    blocks, shared rowlen, no byte offsets."""
    g = small_conv_graph()
    bp = legalise_for_blocks(plan_dmo(g))
    be = X.get_backend("pallas", layout="blocks")
    specs = be.lower_blocks(bp)
    assert specs and all(s.rowlen == bp.arena_rowlen for s in specs)
    for s in specs:
        assert len(s.in_rows) == len(s.in_off)
        assert s.out_rows
        assert s.out_off + s.out_rows[0] <= bp.total_rows
        for off, (rows, used) in zip(s.in_off, s.in_rows):
            assert off + rows <= bp.total_rows
            assert used <= bp.arena_rowlen


# ---------------------------------------------------------------------------
# Mode plumbing: interpret vs compiled follows the platform
# ---------------------------------------------------------------------------


def _fake_platform(monkeypatch, platform: str, kind: str = "cpu"):
    """Make JAX report ``platform`` (and a first device of ``kind``)."""
    import types

    import jax
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])


def test_default_interpret_follows_platform(monkeypatch):
    from repro.kernels.runtime import default_interpret, resolve_interpret
    assert default_interpret() is True          # the CPU backend
    _fake_platform(monkeypatch, "tpu", "TPU v5 lite")
    assert default_interpret() is False
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True      # an explicit pin wins
    _fake_platform(monkeypatch, "gpu")
    assert default_interpret() is True


def test_pallas_mode_plumbing(monkeypatch):
    from repro.core.exec.pallas_backend import PallasExecutor
    assert PallasExecutor().mode == "interpret"
    assert PallasExecutor(mode="compiled").interpret is False
    with pytest.raises(ValueError, match="unknown pallas mode"):
        PallasExecutor(mode="warp")
    with pytest.raises(ValueError, match="unknown pallas layout"):
        PallasExecutor(layout="diagonal")
    # compiled mode cannot address a flat byte arena
    with pytest.raises(ValueError, match="row-blocked"):
        PallasExecutor(mode="compiled", layout="flat")
    # compiled kernels need a TPU: no silent fallback to the interpreter
    with pytest.raises(RuntimeError, match="need a TPU"):
        PallasExecutor(mode="compiled").execute(plan_dmo(small_conv_graph()))
    # the platform retargets the default-constructed backend
    with monkeypatch.context() as m:
        _fake_platform(m, "tpu", "TPU v5 lite")
        assert PallasExecutor().mode == "compiled"
        assert PallasExecutor().interpret is False
    assert PallasExecutor().mode == "interpret"
    # compiled + a non-legalisable plan must refuse rather than fall back
    g = Graph("mixed")
    a = g.tensor("a", (4, 4), 1, "input")
    b = g.tensor("b", (4, 4), 4, "input")
    g.op("elementwise", [a], (4, 4), dict(fn="relu"), out_kind="output")
    g.op("elementwise", [b], (4, 4), dict(fn="relu"), name="e2",
         out_kind="output")
    g.validate()
    with pytest.raises(ValueError, match="mixed-dtype"):
        PallasExecutor(mode="compiled").execute(plan_dmo(g))


def test_compile_backend_pallas_verifies_blocked_tier():
    from repro.core import pipeline
    cp = pipeline.compile(small_conv_graph(), backend="pallas",
                          verify="numeric", cache=False)
    assert cp.verified == "numeric+pallas"
    assert any("flat + row-blocked" in l for l in cp.log)
    # the report states the legalised (row-blocked) peak + padding overhead
    assert "row-blocked" in cp.report()
    bp = cp.legalised()
    assert bp is not None and bp.padded_peak_bytes >= cp.peak_bytes
