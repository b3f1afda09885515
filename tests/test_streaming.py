"""Streaming grid execution: live-window schedules + the streaming pallas
route (PR 6).

Three layers under test:

- planner: :meth:`BlockPlan.window_schedule` — zoo-wide containment (every
  row an op's streaming program touches stays inside its ``[lo, hi)``
  window and inside the arena; every *valid* kernel tap lands inside the
  fetched rolling window) and the flagship bound ``max_window_rows <
  total_rows`` (the acceptance: the VMEM ceiling is the window, not the
  arena);
- kernels/backend: ``mode="streaming"`` parity — bit-exact vs the
  row-blocked program (same kernel bodies, f32 AND int8) and vs the numpy
  backend (f32 tolerance / int8 <= 1 LSB);
- plumbing: mode validation, the flat-layout refusal, the interpret pin,
  and the VMEM-budget refusals (streaming gates on the window, compiled
  mode on the whole arena).
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import exec as X
from repro.core import planner as P
from repro.core import zoo
from repro.core.graph import Graph, op_pads
from repro.core.pipeline import compile as compile_graph


def allops_graph() -> Graph:
    """Every streamable op kind once: rolling (conv/dw/pool) AND staged
    (elementwise, pad, concat, softmax, matmul, fully_connected, mean)."""
    g = Graph("stream_allops")
    x = g.tensor("x", (16, 16, 8), 4, "input")
    c = g.op("conv2d", [x], (16, 16, 8),
             dict(kernel=(3, 3), stride=(1, 1), padding="same"))
    d = g.op("depthwise_conv2d", [c], (16, 16, 8),
             dict(kernel=(3, 3), stride=(1, 1), padding="same"))
    e = g.op("elementwise", [d, c], (16, 16, 8), dict(fn="add"))
    p = g.op("pool", [e], (8, 8, 8),
             dict(kernel=(2, 2), stride=(2, 2), padding="valid", mode="max"))
    pd = g.op("pad", [p], (10, 10, 8),
              dict(paddings=((1, 1), (1, 1), (0, 0))))
    cc = g.op("concat", [pd, pd], (10, 10, 16), dict(axis=-1))
    m = g.op("mean", [cc], (16,), dict(axes=(0, 1)))
    f = g.op("fully_connected", [m], (12,))
    g.op("softmax", [f], (12,), out_kind="output")
    g.validate()
    return g


#: Executable models spanning both dtype tiers, reduced + flagship.
_MODELS = {
    "mobilenet_v1_0.25_32_f32": lambda: zoo.mobilenet_v1(0.25, 32, 4),
    "mobilenet_v2_0.35_32_f32": lambda: zoo.mobilenet_v2(0.35, 32, 4),
    "mobilenet_v1_0.25_32_8bit": lambda: zoo.mobilenet_v1(0.25, 32, 1),
    "mobilenet_v1_0.25_128_8bit":
        zoo.TABLE3_MODELS["mobilenet_v1_0.25_128_8bit"][0],
    "stream_allops": allops_graph,
}


def _bplan(build):
    cp = compile_graph(build())
    bp = cp.legalised()
    assert bp is not None, "model must legalise for the streaming tests"
    return cp, bp


# ---------------------------------------------------------------------------
# Planner layer: window schedule properties
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(_MODELS))
def test_window_containment(name):
    """Every row the streaming program touches stays inside the op's
    declared ``[lo, hi)`` window and inside the arena — and every tap the
    kernel reads on a *valid* input row lands inside the rolling window
    fetched for that tile (the property that makes streaming reads exact,
    not just by-construction extents)."""
    _, bp = _bplan(_MODELS[name])
    ws = bp.window_schedule()
    sub = bp.tiling[0]
    by_name = {op.name: op for op in bp.order}
    chains = {}
    for op in bp.order:
        cname = op.params.get("fuse_chain")
        if cname is not None:
            chains.setdefault(cname, []).append(op)
    # one window per fused chain, one per remaining non-reshape op
    assert len(ws.windows) == sum(
        1 for op in bp.order if op.kind != "reshape") - sum(
        len(m) - 1 for m in chains.values())
    for w in ws.windows:
        if w.kind == "fused":
            # fused chain: every arena-resident operand (ext inputs + the
            # terminal output) stays inside the declared window
            members = chains[w.op_name]
            internal = {op.output.storage() for op in members[:-1]}
            assert 0 <= w.lo < w.hi <= bp.total_rows
            assert w.lo % sub == 0 and w.hi % sub == 0
            for op in members:
                for t in op.inputs:
                    s = t.storage()
                    if s.kind == "weight" or s in internal:
                        continue
                    lay = bp.layout_of(t)
                    assert w.lo <= lay.row_offset
                    assert lay.row_offset + lay.rows <= w.hi
            out = bp.layout_of(members[-1].output)
            assert w.lo <= out.row_offset
            assert out.row_offset + out.rows <= w.hi
            continue
        op = by_name[w.op_name]
        ins = [t for t in op.inputs if t.storage().kind != "weight"]
        lays = [bp.layout_of(t) for t in ins]
        out = bp.layout_of(op.output)
        assert 0 <= w.lo < w.hi <= bp.total_rows
        assert w.lo % sub == 0 and w.hi % sub == 0
        # operand/output block extents stay inside the window
        for lay in lays + [out]:
            assert w.lo <= lay.row_offset
            assert lay.row_offset + lay.rows <= w.hi
        if not w.rolling:
            continue
        # rolling: fixed-size fetches inside window and arena — tile and
        # window geometry is in *arena* rows, taps come from *image* rows
        # mapped through the operands' packed (cols_per_row, row_span)
        xi = lays[0].row_offset
        ih = int(op.inputs[0].shape[-3])
        oh = int(op.output.shape[-3])
        ci, ki = lays[0].cols_per_row, lays[0].row_span
        tr = P.tile_rows(out.cols_per_row, out.row_span, sub)
        wb, slot_rows = P.tile_writeback(out.row_offset, oh,
                                         out.cols_per_row, out.row_span,
                                         sub, bp.total_rows)
        win_in = w.win_rows - P.tile_arena_rows(
            out.cols_per_row, out.row_span, sub)
        assert len(w.starts) == -(-oh // tr)
        for s in w.starts:
            assert s % P.DMA_ROWS == 0
            assert w.lo <= s and s + win_in <= w.hi
            assert 0 <= s and s + win_in <= bp.total_rows
        # every output tile's rows sit inside its write-back span
        co, ko = out.cols_per_row, out.row_span
        for t, s in enumerate(wb):
            assert s % P.DMA_ROWS == 0 and w.lo <= s
            a, b = t * tr, min((t + 1) * tr, oh)
            assert s <= out.row_offset + P._ar_of(a, co, ko)
            assert out.row_offset + P._ar_top(b - 1, co, ko) < s + slot_rows
            assert s + slot_rows <= w.hi
        # ... and every valid tap of every output row of tile t is
        # resident in tile t's fetched window
        kh, sh, dh, ph = P._roll_geometry(op)
        for t, s in enumerate(w.starts):
            for oy in range(t * tr, min((t + 1) * tr, oh)):
                for fy in range(kh):
                    iy = oy * sh - ph + fy * dh
                    if 0 <= iy < ih:
                        lo_ar = xi + P._ar_of(iy, ci, ki)
                        hi_ar = xi + P._ar_top(iy, ci, ki)
                        assert s <= lo_ar and hi_ar < s + win_in, \
                            f"{op.name}: tap rows [{lo_ar}, {hi_ar}] " \
                            f"outside fetch [{s}, {s + win_in}) at tile {t}"


@pytest.mark.parametrize("name", list(_MODELS))
def test_staged_slots_match_schedule(name):
    """Staged ops: the packed scratch slots are disjoint, ordered, each
    block sits at its arena row modulo DMA_ROWS, and the total the kernel
    allocates equals the schedule's resident rows; the live rows are the
    blocks' own rows."""
    _, bp = _bplan(_MODELS[name])
    ws = bp.window_schedule()
    by_name = {op.name: op for op in bp.order}
    sub = bp.tiling[0]
    chains = {}
    for op in bp.order:
        cname = op.params.get("fuse_chain")
        if cname is not None:
            chains.setdefault(cname, []).append(op)
    for w in ws.windows:
        if w.rolling:
            op = by_name[w.op_name]
            out = bp.layout_of(op.output)
            _, slot_rows = P.tile_writeback(
                out.row_offset, int(op.output.shape[-3]), out.cols_per_row,
                out.row_span, sub, bp.total_rows)
            tile_ar = P.tile_arena_rows(out.cols_per_row, out.row_span, sub)
            assert slot_rows >= tile_ar
            assert w.resident_rows == 2 * (w.win_rows - tile_ar) + slot_rows
            continue
        if w.kind == "fused":
            # fused chains stage the ext inputs + terminal output alongside
            # the chain scratch: the window is the include_io slot total
            # (chain_rows_of applies the packed geometry to scratch tensors
            # exactly as the planner's own _fused_window does)
            members = chains[w.op_name]
            _, total = P.fused_slots(members, P.chain_rows_of(bp),
                                     round_to=sub, include_io=True)
            _, live = P.fused_slots(members, P.chain_rows_of(bp),
                                    round_to=sub, include_io=True,
                                    dma_io=False)
            assert total == w.resident_rows and live == w.win_rows <= total
            continue
        op = by_name[w.op_name]
        ins = [t for t in op.inputs if t.storage().kind != "weight"]
        blocks = [(bp.layout_of(t).row_offset, bp.layout_of(t).rows)
                  for t in ins]
        out_lay = bp.layout_of(op.output)
        out_block = (out_lay.row_offset, out_lay.rows)
        offs, out_slot, total = P.staged_slots(blocks, out_block, sub)
        assert total == w.resident_rows
        assert w.win_rows == -(-sum(r for _, r in blocks + [out_block])
                               // sub) * sub <= total
        cur = 0
        for o, (off, r) in zip(list(offs) + [out_slot],
                               blocks + [out_block]):
            lo, n = P.dma_span(off, r)
            assert o == cur + off - lo
            cur += n
        assert cur <= total


def test_flagship_window_strictly_below_arena():
    """Acceptance: on the paper's flagship 8-bit rows the streaming VMEM
    ceiling (max_resident_bytes) is strictly smaller than what the
    VMEM-resident blocked program needs — the whole arena plus any fused
    chain scratch. Packing can shrink the arena *below* the rolling
    double-buffer (the window/arena row comparison loses meaning there),
    so the strict window-below-arena bound is asserted on the legacy
    layout and packing is held to never raising the streaming ceiling."""
    from repro.core.exec.pallas_backend import PallasExecutor
    for name in zoo.TABLE3_8BIT_MODELS:
        _, bp = _bplan(zoo.TABLE3_MODELS[name][0])
        ws = bp.window_schedule()
        leg = P.legalise_for_blocks(bp.source, packing="legacy")
        ws_leg = leg.window_schedule()
        assert ws_leg.max_window_rows < ws_leg.total_rows, name
        assert ws.max_resident_bytes <= ws_leg.max_resident_bytes, name
        specs = PallasExecutor(layout="blocks",
                               interpret=True).lower_blocks(leg)
        scratch = max((s.scratch_rows for s in specs if s.kind == "fused"),
                      default=0)
        compiled_need = (leg.total_rows + scratch) * leg.row_bytes
        assert ws_leg.max_resident_bytes < compiled_need, name
        assert bp.report().count("streaming windows:") == 1


def test_window_schedule_memoised():
    _, bp = _bplan(_MODELS["mobilenet_v1_0.25_32_f32"])
    assert bp.window_schedule() is bp.window_schedule()


# ---------------------------------------------------------------------------
# Kernel + backend layer: streaming parity
# ---------------------------------------------------------------------------


_PARITY = ("mobilenet_v1_0.25_32_f32", "mobilenet_v1_0.25_32_8bit",
           "mobilenet_v1_0.25_128_8bit", "stream_allops")


@pytest.mark.parametrize("name", _PARITY)
def test_streaming_parity(name):
    """mode="streaming" executes the zoo: bit-exact vs the row-blocked
    program (identical kernel bodies, DMA'd operands) and within tolerance
    vs the numpy arena backend (int8 <= 1 LSB via compare_outputs)."""
    cp, _ = _bplan(_MODELS[name])
    got_blk = X.get_backend("pallas", layout="blocks").execute(cp)
    got_st = X.get_backend("pallas", mode="streaming",
                           interpret=True).execute(cp)
    got_np = X.get_backend("numpy").execute(cp)
    X.compare_outputs(got_blk, got_st, exact=True,
                      label=f"{name} streaming vs blocked")
    X.compare_outputs(got_np, got_st, exact=False,
                      label=f"{name} streaming vs numpy")


def test_lower_stream_grafts_window_statics():
    from repro.core.exec.pallas_backend import PallasExecutor
    _, bp = _bplan(_MODELS["mobilenet_v1_0.25_32_8bit"])
    be = PallasExecutor(mode="streaming", interpret=True)
    specs = be.lower_stream(bp)
    ws = bp.window_schedule()
    assert len(specs) == len(ws.windows)
    for s, w in zip(specs, ws.windows):
        assert s.win_rows == w.win_rows > 0
        assert s.win_lo == w.lo
        assert s.win_starts == w.starts
        if s.kind in ("conv2d", "depthwise_conv2d", "pool"):
            assert s.win_starts, f"{s.kind} should roll"
    # the blocked lowering stays streaming-free
    assert all(s.win_rows == 0 for s in be.lower_blocks(bp))


# ---------------------------------------------------------------------------
# Plumbing: modes, layouts, budgets
# ---------------------------------------------------------------------------


def test_streaming_mode_plumbing(monkeypatch):
    from repro.core.exec.pallas_backend import PallasExecutor
    with pytest.raises(ValueError, match="unknown pallas mode"):
        PallasExecutor(mode="stream")
    with pytest.raises(ValueError, match="row-blocked"):
        PallasExecutor(mode="streaming", layout="flat")
    # interpret-ness: a pin beats the platform, else the platform decides
    assert PallasExecutor(mode="streaming").interpret          # CPU
    assert not PallasExecutor(mode="streaming", interpret=False).interpret
    with monkeypatch.context() as m:
        _fake_tpu(m, "TPU v5 lite")
        assert not PallasExecutor(mode="streaming").interpret
        assert PallasExecutor(mode="streaming", interpret=True).interpret


def test_streaming_refuses_over_budget_window():
    """The streaming gate is the *window*, not the arena: a budget between
    the two refuses compiled-style whole-arena residency but admits
    streaming; a budget below the window refuses streaming too."""
    from repro.core.exec.pallas_backend import PallasExecutor
    # 96px v2 build: big enough that the double-buffered resident scratch
    # is strictly below the compiled-mode need — the packed layouts shrink
    # the mobilenet_v1 arenas to the point where the two tie
    cp, bp = _bplan(lambda: zoo.mobilenet_v2(0.35, 96, 1))
    ws = bp.window_schedule()
    # compiled mode must keep the whole arena plus any fused chain scratch
    # resident; streaming only the largest window
    specs = PallasExecutor(layout="blocks", interpret=True).lower_blocks(bp)
    scratch = max((s.scratch_rows for s in specs if s.kind == "fused"),
                  default=0)
    compiled_need = (bp.total_rows + scratch) * bp.row_bytes
    assert ws.max_resident_bytes < compiled_need
    with pytest.raises(ValueError, match="does not fit VMEM"):
        PallasExecutor(mode="streaming", interpret=True,
                       vmem_budget=ws.max_resident_bytes - 1).execute(cp)
    with pytest.raises(ValueError, match="streaming"):
        PallasExecutor(mode="compiled",
                       vmem_budget=compiled_need - 1).execute(cp)
    # between window and compiled need: streaming executes where compiled
    # refuses
    out = PallasExecutor(mode="streaming", interpret=True,
                         vmem_budget=compiled_need - 1).execute(cp)
    ref = X.get_backend("numpy").execute(cp)
    X.compare_outputs(ref, out, exact=False, label="budget-admitted stream")


def _fake_tpu(monkeypatch, kind: str):
    """Make JAX report one TPU device of ``kind``."""
    import types

    import jax
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])


def test_vmem_budget_from_device_kind_table(monkeypatch):
    """The VMEM budget is the device kind's row of the one table; the CPU
    (interpret mode) gates on the chip it stands in for; an explicit
    budget wins."""
    from repro.core.exec import pallas_backend as PB
    from repro.kernels import runtime
    be = PB.PallasExecutor(mode="streaming", interpret=True)
    v5e = runtime.VMEM_LIMIT_BYTES["TPU v5 lite"]
    assert be._resolve_budget() == v5e
    assert runtime.vmem_limit(runtime.INTERPRET_TARGET) == v5e
    with monkeypatch.context() as m:
        _fake_tpu(m, "TPU v5 lite")
        assert be._resolve_budget() == v5e
    assert PB.PallasExecutor(vmem_budget=99)._resolve_budget() == 99


def test_unknown_tpu_kind_raises(monkeypatch):
    """A TPU the VMEM table does not name is an error, not a default."""
    from repro.core.exec import pallas_backend as PB
    from repro.kernels import runtime
    with pytest.raises(ValueError, match="no VMEM limit"):
        runtime.vmem_limit("TPU v99")
    _fake_tpu(monkeypatch, "TPU v99")
    with pytest.raises(ValueError, match="no VMEM limit"):
        PB.PallasExecutor(mode="compiled")._resolve_budget()


def test_verify_pass_covers_streaming_tier():
    """Compiling for backend="pallas" now cross-checks the streaming tier
    too (the acceptance path CPU CI runs)."""
    cp = compile_graph(_MODELS["mobilenet_v1_0.25_32_8bit"](),
                      backend="pallas", verify="numeric")
    assert any("streaming" in line for line in cp.log), cp.log
    assert cp.verified == "numeric+pallas"
