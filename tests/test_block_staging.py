"""Row-blocked launches stage only their operands' rows.

Each launch of the row-blocked (compiled) program copies into its VMEM
image of the arena the DMA-aligned spans of its input blocks and of its
output block, and copies back only the output block's span. Under test:

- the spans themselves (:func:`repro.kernels.arena_ops.block_stage_spans`):
  merged where they overlap or touch, aligned to whole DMA row groups;
- that no kernel body reads a row no copy filled: every row-blocked
  kernel runs in the TPU interpreter with its VMEM starting as NaN (the
  largest integer for an int8 arena), and the outputs still match the
  NumPy backend, while a staging that leaves the inputs out does not;
- that the arithmetic is unchanged: at batch 2 the row-blocked outputs
  equal the streaming route's exactly (both in one interpreter, since the
  two interpreters may round a dot product differently).
"""
from __future__ import annotations

import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import exec as X
from repro.core import zoo
from repro.core.pipeline import compile as compile_graph
from repro.kernels import arena_ops
from test_streaming import allops_graph

#: (graph constructor, batch) per case: both dtype tiers at the flagship's
#: size, each with its fused band chain; every op kind at batch 2; and
#: Inception-ResNet-v2 at 75 px with one block of each kind (joins, pools,
#: asymmetric convs).
CASES = {
    "mobilenet_v1_0.25_128_f32": (lambda: zoo.mobilenet_v1(0.25, 128, 4), 1),
    "mobilenet_v1_0.25_128_8bit":
        (zoo.TABLE3_MODELS["mobilenet_v1_0.25_128_8bit"][0], 1),
    "stream_allops_b2": (allops_graph, 2),
    "inception_resnet_v2_75_f32":
        (lambda: zoo.inception_resnet_v2(75, 4, (1, 1, 1)), 1),
}


@pytest.fixture
def nan_vmem(monkeypatch):
    """Interpret every row-blocked kernel in the TPU interpreter with its
    VMEM starting as NaN, so that a read of a row no copy filled poisons
    the result. Programs lowered before or under the patch are dropped on
    both sides."""
    real = pl.pallas_call

    def pallas_call(kernel, *args, **kw):
        if (kw.get("interpret") is True
                and getattr(kernel, "func", None) is arena_ops._block_kernel):
            kw["interpret"] = pltpu.InterpretParams(uninitialized_memory="nan")
        return real(kernel, *args, **kw)

    arena_ops._lower_program_cached.cache_clear()
    monkeypatch.setattr(pl, "pallas_call", pallas_call)
    yield
    arena_ops._lower_program_cached.cache_clear()


def _spec(in_blocks, out_block):
    """A row-blocked spec with the given ``(row offset, rows)`` blocks."""
    return arena_ops.OpSpec(
        kind="elementwise", in_off=tuple(o for o, _ in in_blocks),
        in_shape=((1,),) * len(in_blocks), out_off=out_block[0],
        out_shape=(1,), rowlen=128,
        in_rows=tuple((r, 128) for _, r in in_blocks),
        out_rows=(out_block[1], 128))


@pytest.mark.parametrize("ins,out,total,spans,back", [
    # a diagonal overlap: the output block starts inside the input's
    # span, so the two are copied as one
    ([(0, 20)], (12, 20), 32, ((0, 32),), (8, 24)),
    # blocks in one 8-row group, and blocks that only touch, merge too
    ([(3, 2)], (5, 1), 8, ((0, 8),), (0, 8)),
    ([(0, 8), (8, 8)], (16, 8), 24, ((0, 24),), (16, 8)),
    # disjoint blocks stay apart, sorted by row
    ([(40, 4), (0, 9)], (24, 3), 48, ((0, 16), (24, 8), (40, 8)), (24, 8)),
    # an arena that ends inside a group: the last span stops at its end
    ([(8, 10)], (0, 3), 18, ((0, 18),), (0, 8)),
])
def test_stage_spans_merge_aligned_blocks(ins, out, total, spans, back):
    spec = _spec(ins, out)
    assert arena_ops.block_stage_spans(spec, total) == (spans, back)
    assert arena_ops.block_stage_bytes(spec, total) == (
        sum(n for _, n in spans) + back[1]) * 128 * 4


@pytest.mark.parametrize("name", list(CASES))
def test_blocked_route_reads_only_staged_rows(nan_vmem, name):
    """With every VMEM buffer of the row-blocked kernels starting as NaN,
    the program's outputs match the NumPy backend (f32 tolerance, int8 <=
    1 LSB)."""
    build, batch = CASES[name]
    cp = compile_graph(build(), batch=batch, cache=False)
    got = X.get_backend("pallas", layout="blocks").execute(cp)
    X.compare_outputs(X.get_backend("numpy").execute(cp), got, exact=False,
                      label=f"{name} blocked vs numpy")
    if name.startswith("mobilenet_v1_0.25_128"):
        assert cp.winner == "fuse", "the flagship size runs a fused chain"


def test_blocked_route_equals_streaming_at_batch_2():
    """Every op kind at batch 2: the row-blocked program's outputs equal
    the streaming route's bit for bit (the same kernel bodies, each route
    staging its own rows)."""
    cp = compile_graph(allops_graph(), batch=2, cache=False)
    X.compare_outputs(
        X.get_backend("pallas", mode="streaming", interpret=True).execute(cp),
        X.get_backend("pallas", layout="blocks").execute(cp), exact=True,
        label="batch 2 blocked vs streaming")


def test_nan_vmem_catches_an_unstaged_input(nan_vmem, monkeypatch):
    """The check above bites: staging only the output block's span leaves
    the inputs' rows NaN, and the outputs with them."""
    real = arena_ops.block_stage_spans

    def output_only(spec, total_rows):
        out = real(spec, total_rows)[1]
        return (out,), out

    monkeypatch.setattr(arena_ops, "block_stage_spans", output_only)
    cp = compile_graph(zoo.mobilenet_v1(0.25, 32, 4), cache=False)
    got = X.get_backend("pallas", layout="blocks").execute(cp)
    assert all(np.isnan(v).all() for v in got.values())
