"""Fused band-chain super-kernels (the FusePass + one-Pallas-call-per-chain
execution path).

Covers: chain discovery on split graphs, the fused-graph rewrite (scratch
re-kinding, provenance markers), planner behaviour (intermediates drop out of
placement, fused peak below the split peak), zoo-wide fused-vs-unfused parity
on both backends, streaming window containment for fused chains, the
VMEM-budget refusal with unfused fallback, the launch-count acceptance
numbers, and the per-signature lowering cache.
"""
import numpy as np
import pytest

from repro.core import pipeline, planner as P, zoo
from repro.core import splitting as S
from repro.core.exec import compare_outputs, get_backend
from repro.core.exec import ops as X
from repro.core.graph import band_range
from repro.core.planner import plan_dmo


def _flagship():
    return zoo.TABLE3_MODELS["mobilenet_v1_0.25_128_8bit"][0]()


def _split_flagship():
    sg, rc, _ = S.auto_split(_flagship())
    assert rc > 0, "flagship must split"
    return sg


# ---------------------------------------------------------------------------
# Chain discovery + fused-graph rewrite
# ---------------------------------------------------------------------------


def test_find_band_chains_flagship():
    """The flagship split graph holds one chain: every band pair plus the
    reassembling concat, contiguous in op order."""
    sg = _split_flagship()
    chains = S.find_band_chains(sg)
    assert len(chains) == 1
    ch = chains[0]
    assert ch[-1].kind == "concat"
    assert all(band_range(op) is not None for op in ch[:-1])
    idx = [sg.ops.index(op) for op in ch]
    assert idx == list(range(idx[0], idx[0] + len(ch)))
    assert len(ch) >= 3


def test_find_band_chains_empty_on_unsplit():
    assert S.find_band_chains(_flagship()) == []


def test_fuse_chains_rewrites_scratch_and_markers():
    sg = _split_flagship()
    chains = S.find_band_chains(sg)
    fg = S.fuse_chains(sg, chains)
    assert fg is not None and fg.name.endswith("_fused")
    members = S.chain_members(fg)
    assert len(members) == 1
    (cname, ops), = members.items()
    assert len(ops) == len(chains[0])
    # internal tensors became scratch; the terminal output did not
    internal = {op.output.storage() for op in ops[:-1]}
    assert all(s.kind == "scratch" for s in internal)
    assert ops[-1].output.storage().kind != "scratch"
    # provenance markers: chain name + ascending stage index
    assert ops[-1].name == cname
    assert [op.params["fuse_stage"] for op in ops] == list(range(len(ops)))
    # scratch never reaches arena placement or scopes
    assert not any(s.kind == "scratch" for s in fg.arena_tensors())
    assert not any(s.kind == "scratch" for s in fg.scopes())


def test_fused_peak_below_split_peak():
    """Tentpole acceptance: dropping chain intermediates out of placement
    pushes the banded arena peak below the O_s-only split peak — and on the
    flagship below the 53 KB relaxed split peak of the previous release."""
    sg = _split_flagship()
    fg = S.fuse_chains(sg)
    split_peak = plan_dmo(sg).peak_bytes
    fused_peak = plan_dmo(fg).peak_bytes
    assert fused_peak < split_peak
    assert fused_peak <= 53 * 1024


def test_fused_slots_pack_tight_and_round_total():
    """fused_slots packs member-local liveness tightly (slots byte/row
    granular) and only rounds the total."""
    sg = _split_flagship()
    fg = S.fuse_chains(sg)
    (_, members), = S.chain_members(fg).items()
    rows_of = lambda s: int(s.shape[-3])
    slots, total = P.fused_slots(members, rows_of, round_to=8)
    internal = {op.output.storage() for op in members[:-1]}
    assert set(slots) == internal
    assert total % 8 == 0
    assert max(slots[s] + rows_of(s) for s in internal) <= total
    # liveness overlap => strictly better than sum of sizes
    assert total < sum(rows_of(s) for s in internal) + 8


# ---------------------------------------------------------------------------
# Parity: fused vs unfused, both backends, both dtype tiers
# ---------------------------------------------------------------------------


_PARITY_MODELS = {
    "mobilenet_v1_0.25_64_f32": lambda: zoo.mobilenet_v1(0.25, 64, 4),
    "mobilenet_v1_0.25_64_8bit": lambda: zoo.mobilenet_v1(0.25, 64, 1),
    "mobilenet_v2_0.35_32_f32": lambda: zoo.mobilenet_v2(0.35, 32, 4),
    "mobilenet_v1_0.25_128_8bit": _flagship,
}


@pytest.mark.parametrize("name", list(_PARITY_MODELS))
def test_fused_parity_zoo(name):
    """Fused execution matches the unfused split execution on every backend
    route: numpy bit-exact per tier (f32 exact, int8 <= 1 LSB via
    compare_outputs), pallas blocked + streaming within the same tolerance."""
    g = _PARITY_MODELS[name]()
    sg, _, _ = S.auto_split(g)
    if not S.find_band_chains(sg):
        pytest.skip(f"{name} has no fusable band chain")
    fg = S.fuse_chains(sg)
    assert fg is not None
    sp, fp = plan_dmo(sg), plan_dmo(fg)
    ref = get_backend("numpy").execute(sp)
    f32 = not X.needs_quant(sg)
    for label, got in [
        ("numpy", get_backend("numpy").execute(fp)),
        ("pallas-blocked",
         get_backend("pallas", layout="blocks").execute(fp)),
        ("pallas-streaming",
         get_backend("pallas", mode="streaming", interpret=True).execute(fp)),
    ]:
        exact = f32 and label == "numpy"
        compare_outputs(ref, got, exact=exact,
                        label=f"{name} fused {label} vs unfused numpy")


def test_fused_streaming_window_containment():
    """The fused streaming window stages exactly the include_io slot total
    (ext inputs + chain scratch + terminal output) and stays inside the
    arena extents of its external operands."""
    cp = pipeline.compile(_flagship(), cache=False)
    assert cp.winner == "fuse"
    bp = cp.legalised()
    ws = bp.window_schedule()
    fused = [w for w in ws.windows if w.kind == "fused"]
    assert len(fused) == 1
    w = fused[0]
    members = [op for op in bp.order
               if op.params.get("fuse_chain") == w.op_name]
    internal = {op.output.storage() for op in members[:-1]}

    # chain_rows_of applies the packed (cols_per_row, row_span) geometry to
    # chain-scratch tensors exactly as the planner's _fused_window does
    _, total = P.fused_slots(members, P.chain_rows_of(bp),
                             round_to=bp.tiling[0], include_io=True)
    _, live = P.fused_slots(members, P.chain_rows_of(bp),
                            round_to=bp.tiling[0], include_io=True,
                            dma_io=False)
    assert w.resident_rows == total and w.win_rows == live <= total
    for op in members:
        for t in list(op.inputs) + [op.output]:
            s = t.storage()
            if s.kind == "weight" or s in internal:
                continue
            lay = bp.layout_of(t)
            assert w.lo <= lay.row_offset
            assert lay.row_offset + lay.rows <= w.hi


# ---------------------------------------------------------------------------
# Pipeline: FusePass, budget refusal, winner selection
# ---------------------------------------------------------------------------


def test_pipeline_fuse_winner_and_log():
    cp = pipeline.compile(_flagship(), cache=False)
    assert cp.winner == "fuse"
    assert cp.recompute_elems > 0
    assert any("-> 1 fused kernel" in l for l in cp.log), cp.log
    assert cp.peak_bytes <= 53 * 1024
    assert cp.peak_bytes < cp.baseline_bytes


def test_pipeline_fuse_off_restores_split():
    cp = pipeline.compile(_flagship(), cache=False, fuse="off")
    assert cp.winner == "split"
    assert any("fuse: disabled" in l for l in cp.log)


def test_over_budget_chain_refused_with_fallback():
    """Negative: a VMEM budget below the chain's scratch estimate leaves the
    chain unfused — the pipeline logs the refusal and falls back to the
    plain split variant."""
    cp = pipeline.compile(_flagship(), cache=False, fuse_vmem_budget=1024)
    assert cp.winner == "split"
    assert any("refused" in l and "VMEM budget" in l for l in cp.log), cp.log
    ref = get_backend("numpy").execute(
        pipeline.compile(_flagship(), cache=False))
    got = get_backend("numpy").execute(cp)
    compare_outputs(ref, got, exact=False,
                    label="over-budget fallback vs fused")


def test_fuse_option_validation():
    with pytest.raises(ValueError, match="fuse"):
        pipeline.compile(_flagship(), cache=False, fuse="maybe")


# ---------------------------------------------------------------------------
# Launch counts + lowering cache
# ---------------------------------------------------------------------------


def test_flagship_launch_count_collapse():
    """Acceptance: the split-band region that PR 5 executed as one
    pallas_call per band op becomes ONE fused call — a >= 4x drop — and the
    whole-graph launch count falls accordingly."""
    from repro.core.exec.pallas_backend import PallasExecutor
    cp = pipeline.compile(_flagship(), cache=False)
    bp = cp.legalised()
    specs = PallasExecutor(layout="blocks", interpret=True).lower_blocks(bp)
    fused = [s for s in specs if s.kind == "fused"]
    assert len(fused) == 1
    chain_len = len(fused[0].stages)
    assert chain_len >= 4 * len(fused), \
        f"region launch drop {chain_len} -> {len(fused)} below 4x"
    n_ops = sum(1 for op in bp.order if op.kind != "reshape")
    assert len(specs) == n_ops - (chain_len - 1)


def test_fused_spec_stage_wiring():
    """The fused OpSpec carries per-stage scratch routing: intermediates
    read/write scratch, ext inputs and the terminal concat hit the arena."""
    from repro.core.exec.pallas_backend import PallasExecutor
    cp = pipeline.compile(_flagship(), cache=False)
    bp = cp.legalised()
    specs = PallasExecutor(layout="blocks", interpret=True).lower_blocks(bp)
    spec = next(s for s in specs if s.kind == "fused")
    assert spec.scratch_rows > 0
    stages = spec.stages
    assert not any(stages[0].in_scratch)
    assert all(st.out_scratch for st in stages[:-1])
    assert not stages[-1].out_scratch
    assert all(stages[-1].in_scratch)


# ---------------------------------------------------------------------------
# Tooling: bench differ + trace routes
# ---------------------------------------------------------------------------


def _load_script(name):
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "scripts" / \
        f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_diff_gates_regressions():
    bd = _load_script("bench_diff")
    old = {"models": {"m": {"dmo_kb": 100.0, "launches": 20,
                            "baseline_kb": 96.0, "saving_pct": 50.0}}}

    def with_m(**kw):
        entry = dict(old["models"]["m"])
        entry.update(kw)
        return {"models": {"m": entry}}

    reg, imp = bd.diff(old, with_m(dmo_kb=110.0, launches=10))
    assert any("dmo_kb" in r for r in reg) and len(reg) == 1
    assert any("launches" in i for i in imp)
    # within the 5% default threshold: clean
    reg, _ = bd.diff(old, with_m(dmo_kb=104.0))
    assert not reg
    # --skip silences a documented trade-off
    reg, _ = bd.diff(old, with_m(dmo_kb=110.0), skip=("dmo_kb",))
    assert not reg
    # baseline_kb drift fails in BOTH directions (graph-derived invariant)
    reg, imp = bd.diff(old, with_m(baseline_kb=80.0))
    assert any("baseline_kb" in r for r in reg) and not imp
    # timing metrics only gate under timing=True
    old_t = {"models": {}, "exec_us_per_call": {"i8/pallas_blocks": 100.0}}
    new_t = {"models": {}, "exec_us_per_call": {"i8/pallas_blocks": 200.0}}
    assert bd.diff(old_t, new_t) == ([], [])
    reg, _ = bd.diff(old_t, new_t, timing=True)
    assert reg


def test_export_trace_pallas_routes():
    """The pallas trace routes emit one span per *launch* (not per op) and
    the fused route refuses graphs without fused chains."""
    et = _load_script("export_trace")
    cp = pipeline.compile(zoo.mobilenet_v1(0.25, 32, 1), cache=False)
    ev = et.trace_pallas_events(cp, "blocked")
    spans = [e for e in ev if e["ph"] == "X"]
    n_ops = sum(1 for op in cp.plan.order if op.kind != "reshape")
    assert 0 < len(spans) <= n_ops
    assert all(e["args"]["route"] == "blocked" for e in spans)
    counters = [e for e in ev if e["name"] == "pallas_launches"]
    assert counters[-1]["args"]["launches"] == len(spans)
    cp_nosplit = pipeline.compile(zoo.mobilenet_v1(0.25, 32, 1),
                                  cache=False, split="off")
    with pytest.raises(SystemExit, match="no fused band chains"):
        et.trace_pallas_events(cp_nosplit, "fused")


def test_export_trace_cli_runs_a_pallas_route(tmp_path):
    """The command line reaches the pallas routes, not only numpy/serve."""
    import json
    et = _load_script("export_trace")
    out = tmp_path / "trace.json"
    et.main(["--route", "blocked", "--out", str(out)])
    doc = json.loads(out.read_text())
    assert doc["otherData"]["route"] == "blocked"
    assert any(e["ph"] == "X" and e["args"]["route"] == "blocked"
               for e in doc["traceEvents"])


def test_lowering_cache_hits_across_executes():
    """Satellite: lowered specs are cached per (plan, route, quant)
    signature — a second execute() of the same plan reuses them."""
    from repro.core.exec.pallas_backend import PallasExecutor
    cp = pipeline.compile(zoo.mobilenet_v1(0.25, 32, 1), cache=False)
    be = PallasExecutor(layout="blocks", interpret=True)
    a = be.execute(cp)
    info1 = be.stats()
    b = be.execute(cp)
    info2 = be.stats()
    assert info1["lowering_misses"] == 1 and info1["lowering_hits"] == 0
    assert info2["lowering_misses"] == 1 and info2["lowering_hits"] == 1
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
