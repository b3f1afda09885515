"""Pallas arena executor: lower a plan to kernels over ONE donated buffer.

Three arena programs share the backend (see :mod:`repro.kernels.arena_ops`):

- **row-blocked** (the default whenever the plan legalises): the plan is
  passed through :func:`repro.core.planner.legalise_for_blocks`, giving
  every tensor a ``(rows, rowlen)`` block at a sublane-tile-aligned row
  offset over one typed 2-D arena ((8, 128) f32 / (32, 128) int8 tiles).
  Kernels address whole arena rows via ``pl.dslice`` — no byte bitcasts —
  so the same program lowers under ``interpret=False``: this is the
  compiled-mode path, the TPU-VMEM realisation of the paper's SRAM arena.
  Each launch holds an arena-sized VMEM image, so VMEM caps
  ``total_rows``, but copies only its operands' rows between it and HBM.
- **streaming** (``mode="streaming"``): the same row-blocked layouts, but
  the arena lives in ``pl.ANY`` (HBM) and each op DMAs only its *live
  window* (:meth:`repro.core.planner.BlockPlan.window_schedule`) into VMEM
  scratch with double-buffered ``make_async_copy``. The VMEM gate becomes
  the schedule's ``max_resident_bytes`` instead of the whole arena — the
  refactor that turns the ~16 MB arena ceiling into a window ceiling.
- **flat** (fallback, and the cross-check reference): the byte-granular
  program over a 1-D uint8 arena of exactly ``plan.peak_bytes``; kernels
  bitcast their windows to the tier each layout declares, so mixed-dtype
  plans execute in one buffer. Byte-granular dynamic slices fight the VMEM
  tilings, so this program is interpret-mode only.

Execution mode is ``mode="interpret"`` (the Pallas interpreter, run on
the host CPU), ``mode="compiled"`` (Mosaic lowering on a TPU; requires
row-blocked layouts), or ``mode="streaming"`` (interpreted or compiled).
Unpinned, the mode follows the platform (:mod:`repro.kernels.runtime`):
interpret on the CPU backend, compiled on a TPU. The VMEM budget the
compiled and streaming gates check against — and the scoped-VMEM limit
handed to Mosaic — is ``vmem_budget`` bytes, by default the device kind's
row of :data:`repro.kernels.runtime.VMEM_LIMIT_BYTES`.

Split row bands lower like any conv/pool: ``_canon_meta`` takes the op's
geometry from the band-aware :func:`repro.core.exec.ops.pads`, so a band's
OpSpec carries its band shapes plus the explicit band-local pads (negative
leading row pad for producer bands) and the ordinary row kernels index
exactly the band's rows — in both the flat and the row-blocked program.

In either program the spec sequence jit-compiles to ``fn(arena, *weights)``
with the arena argument donated and every kernel aliasing its arena operand
(``input_output_aliases={0: 0}``), so the entire network executes inside one
buffer — the planner's peak (padded to whole rows in blocked mode) *is* the
runtime footprint, overlaps included. Row loops are sequential
``fori_loop``s — see the §III.F multi-threading caveat in
:mod:`repro.kernels.arena_ops`.

:meth:`PallasExecutor.execute` runs in seven phases, each wrapped in a
``jax.profiler.TraceAnnotation`` that a profiler session records on the
host plane, on the same clock as the device's ops: ``dmo.resolve``
(plan, parameters, the resident filters or else the weight list),
``dmo.legalise`` (row-blocked layouts, lowered specs, the VMEM gate),
``dmo.seed_arena`` (inputs into the arena), ``dmo.upload`` (the arena to
the device, and the distinct filters on a miss of the resident filters),
``dmo.launch`` (the program lookup and its dispatch), ``dmo.fetch`` (the
wait for the device, the copy back, the call's device buffers freed) and
``dmo.gather`` (outputs out of the arena). Each carries the executor's
call number as ``call``; ``dmo.upload`` and ``dmo.fetch`` carry their
``bytes``. With no profiler running a span costs about a microsecond.
:meth:`PallasExecutor.stats` counts the same calls on the host.

Filters stay resident on the device across calls: the first call with a
given plan, weights dict and quantisation uploads each distinct filter
once, and later calls passing the same objects reuse those buffers, so
only the seeded arena crosses to the device. Only the arena is donated,
so a launch never consumes a resident filter. The filters are therefore
treated as immutable for as long as the same objects are passed, as the
lowered-spec cache already bakes ``quant``'s zero points: to change a
filter, pass a new weights dict or ``QuantSpec``.
"""
from __future__ import annotations

import collections
import time
import warnings
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.exec import ops as X
from repro.core.exec import unwrap_plan
from repro.core.graph import Op
from repro.core.planner import (BlockPlan, Plan, chain_addr_of,
                                chain_image_rows_of, fused_slots,
                                legalise_for_blocks, tile_rows)
from repro.kernels import runtime

#: Parameter sets (plan, weights, quantisation) whose filters an executor
#: keeps on the device at once; the oldest is freed first.
RESIDENT_PARAM_SETS = 8


def _fused_chains(order: Sequence[Op]) -> Dict[str, List[Op]]:
    """Chain-name -> members (in order) for a fused graph's execution order,
    with the contiguity check the weight flattening relies on: a chain's
    members must be consecutive in the order so the fused spec (emitted at
    the first member's position) consumes consecutive stage weights from the
    flattened weight list."""
    chains: Dict[str, List[Op]] = {}
    pos: Dict[str, int] = {}
    for i, op in enumerate(order):
        cname = op.params.get("fuse_chain")
        if cname is None:
            continue
        if cname in pos:
            assert pos[cname] == i - 1, \
                f"fused chain {cname!r} is not contiguous in execution order"
        pos[cname] = i
        chains.setdefault(cname, []).append(op)
    return chains


def _addr_triple(lay) -> Tuple[int, int, int]:
    """A layout's packed-addressing spec triple
    ``(cols_per_row, row_span, image_rowlen)``."""
    return (lay.cols_per_row, lay.row_span, lay.image_rowlen)


def _canon_meta(op: Op) -> Tuple:
    """Kind-specific static parameters for the kernel (see arena_ops)."""
    k = op.kind
    if k in ("conv2d", "depthwise_conv2d"):
        kh, kw = op.params["kernel"]
        sh, sw = op.params.get("stride", (1, 1))
        dh, dw = op.params.get("dilation", (1, 1))
        ph, pw = X.pads(op)
        return (kh, kw, sh, sw, dh, dw, ph, pw,
                op.params.get("multiplier", 1))
    if k == "pool":
        kh, kw = op.params["kernel"]
        sh, sw = op.params.get("stride", (1, 1))
        ph, pw = X.pads(op)
        return (kh, kw, sh, sw, ph, pw, op.params.get("mode", "avg"))
    if k == "elementwise":
        return (op.params.get("fn", "relu"),)
    if k == "concat":
        return (op.params.get("axis", -1),)
    if k == "pad":
        return (tuple(tuple(p) for p in op.params["paddings"]),)
    if k == "mean":
        x = op.inputs[0]
        return (tuple(op.params.get("axes", range(len(x.shape) - 1))),)
    return ()


def _canon_qmeta(op: Op, q: Optional[X.OpQuant]) -> Tuple:
    """Hashable quantisation statics per kind (zero points and the float32
    requantisation multipliers of :func:`repro.core.exec.ops.acc_multiplier`
    / :func:`~repro.core.exec.ops.rescale_q`, so both backends bake the
    bit-identical constants)."""
    if q is None:
        return ()
    k = op.kind
    if k in ("conv2d", "depthwise_conv2d", "fully_connected", "pool", "mean"):
        return (q.ins[0].zero_point, X.acc_multiplier(op, q),
                q.out.zero_point)
    if k == "matmul":
        return (q.ins[0].zero_point, q.ins[1].zero_point,
                X.acc_multiplier(op, q), q.out.zero_point)
    if k in ("elementwise", "softmax"):
        in_q = tuple((qp.scale, qp.zero_point) for qp in q.ins)
        out_q = (q.out.scale, q.out.zero_point)
        return (in_q[0], out_q) if k == "softmax" else (in_q, out_q)
    if k == "concat":
        in_q = tuple((qp.zero_point, X.f32_div(qp.scale, q.out.scale))
                     for qp in q.ins)
        return (in_q, (q.out.zero_point,))
    if k == "pad":
        return ((q.ins[0].zero_point,
                 X.f32_div(q.ins[0].scale, q.out.scale)),
                (q.out.zero_point,))
    return ()


class PallasExecutor:
    """The ``pallas`` :class:`~repro.core.exec.ArenaExecutor` backend.

    ``mode``: ``"interpret"`` (the Pallas interpreter on the host CPU),
    ``"compiled"`` (Mosaic lowering on a TPU), or ``"streaming"``
    (ANY-space arena, live windows DMA'd into VMEM scratch; runs
    interpreted or compiled — pass ``interpret=`` to pin it, else the
    platform decides). ``None`` follows the platform: interpret on the CPU
    backend, compiled on a TPU. ``layout``:
    ``"auto"`` runs the row-blocked program whenever the plan legalises
    (uniform dtype, no aggregated views) and falls back to the flat byte
    program otherwise; ``"blocks"`` / ``"flat"`` force one program. The
    legalisation itself prefers *packed* row layouts (planner
    ``packing="auto"``) and reverts to the legacy one-image-row-per-arena-
    row layout whenever packing fails to reduce the padded peak.
    Compiled and streaming modes require the row-blocked program — a flat
    byte arena cannot meet the VMEM tilings. ``vmem_budget`` (bytes, by
    default the device kind's VMEM limit) gates execution: compiled mode
    refuses a launch whose arena, chain scratch and weights exceed it,
    streaming mode refuses only schedules whose ``max_resident_bytes``
    exceeds it."""

    name = "pallas"

    def __init__(self, interpret: Optional[bool] = None,
                 mode: Optional[str] = None, layout: str = "auto",
                 vmem_budget: Optional[int] = None):
        if mode is not None and mode not in ("interpret", "compiled",
                                             "streaming"):
            raise ValueError(f"unknown pallas mode {mode!r} (expected "
                             "'interpret', 'compiled' or 'streaming')")
        if layout not in ("auto", "blocks", "flat"):
            raise ValueError(f"unknown pallas layout {layout!r} "
                             "(expected 'auto', 'blocks' or 'flat')")
        if mode is None and interpret is not None:
            mode = "interpret" if interpret else "compiled"
        #: None = follow the platform JAX runs on
        self._mode = mode
        self._interpret = interpret     # explicit pin (streaming mode only)
        self.layout = layout
        self.vmem_budget = vmem_budget
        #: Lowered-spec cache across execute() calls: (plan identity, route,
        #: quant identity) -> spec tuple. Values pin the plan/quant objects
        #: so the id() keys stay valid; bounded FIFO. Together with the
        #: content-addressed jit cache in arena_ops.lower_program this makes
        #: repeated executions of one compiled plan re-trace nothing.
        self._lowered: "collections.OrderedDict" = collections.OrderedDict()
        #: synth_weights/calibrate results per (plan identity, seed) — both
        #: are deterministic, so repeat executions skip calibration too.
        self._autoparams: "collections.OrderedDict" = collections.OrderedDict()
        #: Device-resident filters across execute() calls: (plan, weights,
        #: quant identity, interpreted) -> (plan, weights, quant, the
        #: device arrays in weight-list order, one buffer per distinct
        #: filter). Values pin the keyed objects so the id() keys stay
        #: valid; bounded FIFO, an evicted entry frees its buffers.
        self._resident: "collections.OrderedDict" = collections.OrderedDict()
        #: jitted programs this executor has run (a call that runs one not
        #: in here traces, lowers and compiles it, or loads it from JAX's
        #: compilation cache)
        self._programs: "weakref.WeakSet" = weakref.WeakSet()
        self._stats: Dict[str, float] = dict.fromkeys(
            ("calls", "images", "h2d_bytes", "d2h_bytes", "uploads",
             "weight_hits", "weight_misses", "lowering_hits",
             "lowering_misses", "programs_built", "stage_bytes"), 0)
        self._stats["first_call_s"] = 0.0
        self._check_mode_layout()

    def stats(self) -> Dict[str, float]:
        """Counters over this executor's :meth:`execute` calls, kept on the
        host with no device sync: ``calls`` (entered) and ``images``
        (returned); ``weight_hits`` and ``weight_misses``, the calls that
        reused the filters resident on the device and those that uploaded
        them (a new plan, weights dict or ``QuantSpec`` object misses; the
        filters behind a hit are taken as unchanged, so do not edit them
        in place); ``h2d_bytes`` (the arena every call, and the distinct
        filters on a miss) and ``d2h_bytes`` (the arena fetched back);
        ``uploads`` (device buffers created: the arena, and one per
        distinct filter on a miss); ``lowering_hits`` and
        ``lowering_misses`` of the lowered-spec cache; ``programs_built``,
        the calls that ran a program this executor had not run before,
        and ``first_call_s``, their host seconds; ``launches.<kind>``, the
        kernels launched by op kind (``launches.conv2d``,
        ``launches.concat``, ``launches.fused`` for a band chain), over
        all calls; ``stage_bytes``, the arena bytes the row-blocked
        launches copied between HBM and VMEM, both directions (the
        streaming route's window copies and the flat program add
        nothing). Read it before and after a window: a ``programs_built``
        or ``weight_misses`` that moved names a recompile or a re-upload."""
        return dict(self._stats)

    @property
    def mode(self) -> str:
        if self._mode is not None:
            return self._mode
        return "interpret" if runtime.default_interpret() else "compiled"

    @property
    def interpret(self) -> bool:
        mode = self.mode
        if mode == "streaming":
            return runtime.resolve_interpret(self._interpret)
        return mode == "interpret"

    def _check_mode_layout(self) -> None:
        if self.mode in ("compiled", "streaming") and self.layout == "flat":
            raise ValueError(
                f"{self.mode} mode requires row-blocked layouts: the flat "
                "byte arena is interpret-only (byte-granular dynamic slices "
                "cannot meet the (8, 128)/(32, 128) VMEM tilings)")

    def _resolve_budget(self) -> int:
        if self.vmem_budget is not None:
            return int(self.vmem_budget)
        return runtime.vmem_limit()

    # -- lowering -----------------------------------------------------------

    @staticmethod
    def _flat_off(plan: Plan, t, b: int) -> int:
        """Byte offset of image ``b`` of a flat-arena operand (batch-1
        operands — weights excluded earlier — are shared across images)."""
        s = t.storage()
        off = plan._layout(t).byte_offset
        return off + b * s.image_nbytes if s.batch > 1 else off

    def lower(self, plan: Plan,
              quant: Optional[X.QuantSpec] = None) -> Tuple:
        """Plan -> flat-program OpSpec sequence (static lowering, no weights
        bound): *byte* offsets per operand. ``quant`` must be supplied for
        plans with int8 ops — its per-op contexts become the kernels' static
        ``qmeta``. A fused band chain lowers to ONE spec (at its first
        member's position) whose stages carry byte offsets into the arena or
        — for scratch-flagged operands — into the chain's scratch buffer.
        Batched ops expand to one per-image spec each (image-minor order,
        ascending — the order the batched O_s is derived against), so the
        kernel bodies never see the batch axis."""
        from repro.kernels.arena_ops import OpSpec
        chains = _fused_chains(plan.order)
        emitted: set = set()
        specs: List[OpSpec] = []
        for op in plan.order:
            if op.kind == "reshape":
                continue
            cname = op.params.get("fuse_chain")
            if cname is not None:
                if cname not in emitted:
                    emitted.add(cname)
                    specs.append(self._fused_flat_spec(
                        plan, chains[cname], quant))
                continue
            assert all(t.storage().kind != "weight" for t in op.inputs), \
                f"{op.name}: non-arena input cannot be lowered"
            lays = [plan._layout(t) for t in op.inputs]
            out = plan._layout(op.output)
            q = X.op_quant(op, quant)
            for b in range(op.output.storage().batch):
                specs.append(OpSpec(
                    kind=op.kind,
                    in_off=tuple(self._flat_off(plan, t, b)
                                 for t in op.inputs),
                    in_shape=tuple(l.shape for l in lays),
                    out_off=self._flat_off(plan, op.output, b),
                    out_shape=out.shape,
                    dtype="i8" if out.dtype_bytes == 1 else "f32",
                    meta=_canon_meta(op),
                    qmeta=_canon_qmeta(op, q),
                    name=op.name))
        return tuple(specs)

    def _fused_flat_spec(self, plan: Plan, members: List[Op],
                         quant: Optional[X.QuantSpec]):
        """One flat-program spec for a fused band chain: stage offsets are
        *byte* offsets — arena placements for external operands, packed
        scratch-byte slots (:func:`repro.core.planner.fused_slots` over the
        batched ``nbytes``) for chain-internal ones. Batched chains expand
        their stages op-major (member-major, image-minor) inside the ONE
        call — the exact order the planner's liveness model and the batched
        O_s derivation assume — so a chain's terminal image-0 write can
        never clobber an external input a later image still reads."""
        from repro.kernels.arena_ops import OpSpec
        cat = members[-1]
        B = cat.output.storage().batch
        internal = {op.output.storage() for op in members[:-1]}
        align = max(s.dtype_bytes for s in internal)
        slots, total = fused_slots(members, lambda s: s.nbytes,
                                   align=align)
        stages: List[OpSpec] = []
        for op in members:
            q = X.op_quant(op, quant)
            for b in range(B):
                in_off, in_scr = [], []
                for t in op.inputs:
                    s = t.storage()
                    if s in internal:
                        in_off.append(slots[s] + b * s.image_nbytes)
                        in_scr.append(1)
                    else:
                        in_off.append(self._flat_off(plan, t, b))
                        in_scr.append(0)
                s_out = op.output.storage()
                if s_out in internal:
                    out_off = slots[s_out] + b * s_out.image_nbytes
                    out_scr = 1
                else:
                    out_off = self._flat_off(plan, op.output, b)
                    out_scr = 0
                stages.append(OpSpec(
                    kind=op.kind,
                    in_off=tuple(in_off),
                    in_shape=tuple(tuple(t.shape) for t in op.inputs),
                    out_off=out_off,
                    out_shape=tuple(op.output.shape),
                    dtype="i8" if op.output.storage().dtype_bytes == 1
                    else "f32",
                    meta=_canon_meta(op),
                    qmeta=_canon_qmeta(op, q),
                    in_scratch=tuple(in_scr),
                    out_scratch=out_scr))
        ext = self._chain_ext_inputs(members, internal)
        out_lay = plan._layout(cat.output)
        return OpSpec(
            kind="fused",
            in_off=tuple(self._flat_off(plan, t, 0) for t in ext),
            in_shape=tuple(tuple(t.shape) for t in ext),
            out_off=self._flat_off(plan, cat.output, 0),
            out_shape=out_lay.shape,
            dtype="i8" if out_lay.dtype_bytes == 1 else "f32",
            meta=(cat.params["fuse_chain"],),
            stages=tuple(stages),
            scratch_rows=total,          # bytes in the flat program
            name=cat.params["fuse_chain"])

    @staticmethod
    def _chain_ext_inputs(members: List[Op], internal) -> List:
        """The chain's external data inputs, deduped in first-read order —
        the DMA order of the streaming fused kernel."""
        ext, seen = [], set()
        for op in members:
            for t in op.inputs:
                s = t.storage()
                if s.kind == "weight" or s in internal or s in seen:
                    continue
                seen.add(s)
                ext.append(t)
        return ext

    def lower_blocks(self, bplan: BlockPlan,
                     quant: Optional[X.QuantSpec] = None) -> Tuple:
        """BlockPlan -> row-blocked OpSpec sequence: arena *row* offsets and
        ``(rows, used)`` block shapes from the legalised
        :class:`~repro.core.planner.BlockLayout` records. A fused band
        chain lowers to ONE spec at its first member's position (stage
        offsets are arena rows, or scratch-slot rows for chain-internal
        operands)."""
        from repro.kernels.arena_ops import OpSpec
        dtype = "i8" if bplan.dtype_bytes == 1 else "f32"
        packed = bplan.packing == "packed"
        sub = bplan.tiling[0]
        chains = _fused_chains(bplan.order)
        emitted: set = set()
        specs: List[OpSpec] = []
        for op in bplan.order:
            if op.kind == "reshape":
                continue
            cname = op.params.get("fuse_chain")
            if cname is not None:
                if cname not in emitted:
                    emitted.add(cname)
                    specs.append(self._fused_block_spec(
                        bplan, chains[cname], quant))
                continue
            ins = [t for t in op.inputs if t.storage().kind != "weight"]
            assert len(ins) == len(op.inputs), \
                f"{op.name}: non-arena input cannot be lowered"
            lays = [bplan.layout_of(t) for t in ins]
            out = bplan.layout_of(op.output)
            q = X.op_quant(op, quant)
            # packed plans carry their addressing triples into the kernels;
            # legacy plans emit the exact pre-packing specs (shared lowering
            # cache, bit-identical programs)
            extra = dict(
                in_addr=tuple(_addr_triple(l) for l in lays),
                out_addr=_addr_triple(out),
                out_tile=tile_rows(out.cols_per_row, out.row_span, sub),
            ) if packed else {}
            # batched ops expand image-minor: each per-image spec addresses
            # image b's padded sub-block (BlockLayout.image_row_offset)
            for b in range(out.batch):
                specs.append(OpSpec(
                    kind=op.kind,
                    in_off=tuple(
                        l.image_row_offset(b if l.batch > 1 else 0)
                        for l in lays),
                    in_shape=tuple(tuple(t.shape) for t in ins),
                    out_off=out.image_row_offset(b),
                    out_shape=tuple(op.output.shape),
                    dtype=dtype,
                    meta=_canon_meta(op),
                    qmeta=_canon_qmeta(op, q),
                    rowlen=bplan.arena_rowlen,
                    in_rows=tuple((l.image_rows, l.rowlen) for l in lays),
                    out_rows=(out.image_rows, out.rowlen),
                    name=op.name,
                    **extra))
        return tuple(specs)

    def _fused_block_spec(self, bplan: BlockPlan, members: List[Op],
                          quant: Optional[X.QuantSpec], window=None):
        """One row-blocked spec for a fused band chain — or, given the
        chain's staged :class:`~repro.core.planner.OpWindow`, the streaming
        variant, whose stages run entirely inside the VMEM scratch buffer
        (every operand gets an ``include_io`` scratch slot; external inputs
        are DMA'd in up front, the terminal output DMA'd back once).
        Scratch slots are sized over the *batched* rows (per-image rows ×
        batch — per-image sub-blocks pack back to back inside a slot);
        stages expand op-major (member-major, image-minor) so the chain
        executes in the exact order the planner's liveness model assumes,
        each stage addressing its image's sub-block."""
        from repro.kernels.arena_ops import OpSpec
        dtype = "i8" if bplan.dtype_bytes == 1 else "f32"
        L = bplan.arena_rowlen
        sub = bplan.tiling[0]
        cat = members[-1]
        B = cat.output.storage().batch
        internal = {op.output.storage() for op in members[:-1]}
        streaming = window is not None
        packed = bplan.packing == "packed"
        irows_of = chain_image_rows_of(bplan)

        def rows_of(s) -> int:
            """Batched slot rows of one chain operand."""
            return irows_of(s) * (s.batch if s.batch > 1 else 1)
        addr_of = chain_addr_of(bplan)

        def triple_of(s):
            """The packed-addressing spec triple of a chain operand —
            arena tensors from their layout, scratch tensors from the
            shared :func:`~repro.core.planner.chain_addr_of` rule."""
            lay = bplan.layouts.get(s)
            if lay is not None:
                return _addr_triple(lay)
            c, k = addr_of(s)
            return (c, k, int(s.shape[-2]) * int(s.shape[-1]))

        def used_of(s):
            lay = bplan.layouts.get(s)
            if lay is not None:
                return lay.rowlen
            c, k, rl = triple_of(s)
            return L if k > 1 else c * rl

        slots, total = fused_slots(members, rows_of, round_to=sub,
                                   include_io=streaming)
        for s in internal:
            assert used_of(s) <= L, \
                f"scratch row of {s.name} wider than the arena row"

        def place(t, b):
            """(offset, (rows, used), scratch?) of one stage operand for
            image ``b`` — scratch-resident operands address their image's
            sub-block inside the batched slot, arena-resident ones the
            image's padded arena sub-block."""
            s = t.storage()
            if s in internal or streaming:
                bb = b if s.batch > 1 else 0
                return (slots[s] + bb * irows_of(s),
                        (irows_of(s), used_of(s)), 1)
            lay = bplan.layouts[s]
            return (lay.image_row_offset(b if lay.batch > 1 else 0),
                    (lay.image_rows, lay.rowlen), 0)

        stages: List[OpSpec] = []
        for op in members:
            q = X.op_quant(op, quant)
            extra = dict(
                in_addr=tuple(triple_of(t.storage()) for t in op.inputs),
                out_addr=triple_of(op.output.storage()),
            ) if packed else {}
            for b in range(B):
                placed = [place(t, b) for t in op.inputs]
                o_off, o_rows, o_scr = place(op.output, b)
                stages.append(OpSpec(
                    kind=op.kind,
                    in_off=tuple(p[0] for p in placed),
                    in_shape=tuple(tuple(t.shape) for t in op.inputs),
                    out_off=o_off,
                    out_shape=tuple(op.output.shape),
                    dtype=dtype,
                    meta=_canon_meta(op),
                    qmeta=_canon_qmeta(op, q),
                    rowlen=L,
                    in_rows=tuple(p[1] for p in placed),
                    out_rows=o_rows,
                    in_scratch=tuple(p[2] for p in placed),
                    out_scratch=o_scr,
                    **extra))
        ext = self._chain_ext_inputs(members, internal)
        out_lay = bplan.layout_of(cat.output)
        # top-level I/O covers the WHOLE batched block of each external
        # operand (per-image sub-blocks are contiguous), so the streaming
        # up-front/write-back DMAs stay one entry per tensor
        spec = OpSpec(
            kind="fused",
            in_off=tuple(bplan.layout_of(t).row_offset for t in ext),
            in_shape=tuple(tuple(t.shape) for t in ext),
            out_off=out_lay.row_offset,
            out_shape=tuple(cat.output.shape),
            dtype=dtype,
            meta=(cat.params["fuse_chain"],),
            rowlen=L,
            in_rows=tuple((bplan.layout_of(t).rows,
                           bplan.layout_of(t).rowlen) for t in ext),
            out_rows=(out_lay.rows, out_lay.rowlen),
            stages=tuple(stages),
            scratch_rows=total,
            name=cat.params["fuse_chain"])
        if streaming:
            import dataclasses
            assert window.resident_rows == total, \
                f"fused window/slot mismatch: {window.resident_rows} vs " \
                f"{total}"
            spec = dataclasses.replace(
                spec, win_lo=window.lo, win_rows=window.win_rows,
                in_slots=tuple(slots[t.storage()] for t in ext),
                out_slot=slots[cat.output.storage()])
        return spec

    def lower_stream(self, bplan: BlockPlan,
                     quant: Optional[X.QuantSpec] = None) -> Tuple:
        """BlockPlan -> streaming OpSpec sequence: the row-blocked specs
        with each op's live-window statics grafted on from the planner's
        :class:`~repro.core.planner.WindowSchedule` (1:1 — both skip
        reshape views and both emit one entry per fused chain), so
        ``win_rows > 0`` selects the streaming grid program in
        :mod:`repro.kernels.arena_ops`. Fused chains are re-lowered in
        their streaming form (all stage operands scratch-resident)."""
        import dataclasses
        specs = self.lower_blocks(bplan, quant)
        ws = bplan.window_schedule()
        chains = _fused_chains(bplan.order)
        assert len(specs) == len(ws.windows), \
            f"spec/window mismatch: {len(specs)} vs {len(ws.windows)}"
        out: List = []
        for s, w in zip(specs, ws.windows):
            if s.kind == "fused":
                out.append(self._fused_block_spec(
                    bplan, chains[w.op_name], quant, window=w))
            else:
                out.append(dataclasses.replace(
                    s, win_lo=w.lo, win_rows=w.win_rows, win_starts=w.starts))
        return tuple(out)

    # -- execution ----------------------------------------------------------

    def _legalised(self, plan: Plan) -> Optional[BlockPlan]:
        """The row-blocked legalisation this call should execute, or None
        for the flat program. An explicit ``layout="flat"`` always runs the
        flat program — a BlockPlan's byte offsets are valid flat offsets —
        so blocked-vs-flat cross-checks stay meaningful. A plan that cannot
        be row-blocked (mixed dtype, aggregated views) raises under
        ``layout="blocks"`` and falls back to flat under ``"auto"`` —
        except in compiled and streaming modes, where flat is not
        lowerable."""
        self._check_mode_layout()   # env-followed mode may have flipped
        if self.layout == "flat":
            return None
        if isinstance(plan, BlockPlan):
            return plan
        try:
            return legalise_for_blocks(plan)
        except ValueError:
            if self.layout == "blocks" or self.mode in ("compiled",
                                                        "streaming"):
                raise
            return None

    def execute(self, plan_or_compiled, inputs=None, weights=None, *,
                seed: int = 0, quant=None) -> Dict[str, np.ndarray]:
        """Run the plan's arena program; returns the output tensors.

        ``weights`` and ``quant`` default to the seed's synthetic ones. The
        filters of a plan, weights dict and ``QuantSpec`` stay on the
        device after the call, and a later call passing the same objects
        uploads only the arena: their arrays must not be edited in place
        while they are passed again. A new weights dict or ``QuantSpec``
        always uploads again."""
        t0 = time.perf_counter()
        import contextlib

        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        from repro.kernels import arena_ops

        st = self._stats
        call = st["calls"]
        st["calls"] += 1
        with TraceAnnotation("dmo.resolve", call=call):
            plan, graph = unwrap_plan(plan_or_compiled)
            reason = X.executability(graph)
            if reason is not None:
                raise ValueError(
                    f"pallas backend cannot lower {graph.name!r}: {reason}")
            if weights is None and quant is None:
                cached = self._autoparams.get((id(plan), seed))
                if cached is not None and cached[0] is plan:
                    weights, quant = cached[1], cached[2]
                else:
                    weights = X.synth_weights(graph, seed)
                    if X.needs_quant(graph):
                        quant = X.calibrate(graph, seed, weights)
                    self._autoparams[(id(plan), seed)] = (plan, weights,
                                                          quant)
                    while len(self._autoparams) > 32:
                        self._autoparams.popitem(last=False)
            if weights is None:
                weights = X.synth_weights(graph, seed)
            if quant is None and X.needs_quant(graph):
                quant = X.calibrate(graph, seed, weights)
            if inputs is None:
                inputs = (X.quant_inputs(graph, quant, seed)
                          if quant is not None
                          else X.random_inputs(graph, seed))
            interpret = self.interpret
            wkey = (id(plan), id(weights), id(quant), interpret)
            resident = self._resident.get(wkey)
            if resident is None:
                st["weight_misses"] += 1
                wflat = self._weight_list(plan, weights, quant)
            else:
                st["weight_hits"] += 1
                wflat = resident[3]

        with TraceAnnotation("dmo.legalise", call=call):
            bplan = self._legalised(plan)
            specs, launches, stage_bytes = self._specs(plan, bplan, quant)
            budget = self._resolve_budget()
            if bplan is not None:
                self._check_vmem(bplan, graph, specs, wflat, budget)
            if not interpret and jax.default_backend() != "tpu":
                raise RuntimeError(
                    f"{self.mode} Pallas kernels need a TPU, and JAX runs "
                    f"on {jax.default_backend()!r}: pass interpret=True (or "
                    "leave the mode to the platform) to interpret them")

        with TraceAnnotation("dmo.seed_arena", call=call):
            if bplan is not None:
                arena = self._seed_block_arena(bplan, graph, inputs)
            else:
                arena = np.zeros(plan.peak_bytes, np.uint8)
                for t in graph.tensors:
                    if t.kind == "input":
                        s, off = t.storage(), plan.offsets[t.storage()]
                        v = np.asarray(inputs[t.name], X.arena_dtype(
                            s.dtype_bytes)).reshape(-1)
                        arena[off:off + s.nbytes] = v.view(np.uint8)

        # interpreted kernels run on the host CPU, never on a TPU
        place = (jax.default_device(runtime.interpret_device()) if interpret
                 else contextlib.nullcontext())
        with warnings.catch_warnings(), place:
            # CPU jit can't honour the donation and warns; the in-kernel
            # aliasing is what carries the single-buffer semantics there
            warnings.filterwarnings("ignore", message=".*donated.*")
            distinct = ([] if resident is not None
                        else list({id(w): w for w in wflat}.values()))
            h2d = arena.nbytes + sum(w.nbytes for w in distinct)
            with TraceAnnotation("dmo.upload", call=call, bytes=h2d):
                if resident is None:
                    on_device = {id(w): jnp.asarray(w) for w in distinct}
                    wflat = tuple(on_device[id(w)] for w in wflat)
                    self._resident[wkey] = (plan, weights, quant, wflat)
                    while len(self._resident) > RESIDENT_PARAM_SETS:
                        self._resident.popitem(last=False)
                arena_in = jnp.asarray(arena)
            st["h2d_bytes"] += h2d
            st["uploads"] += len(distinct) + 1
            with TraceAnnotation("dmo.launch", call=call):
                fn = arena_ops.lower_program(specs, interpret,
                                             None if interpret else budget)
                built = fn not in self._programs
                out = fn(arena_in, *wflat)
                for k, n in launches:
                    st[k] = st.get(k, 0) + n
                st["stage_bytes"] += stage_bytes
            with TraceAnnotation("dmo.fetch", call=call, bytes=out.nbytes):
                out_arena = np.asarray(out)
                # free the call's device buffers inside the span, not
                # unnamed after the last one as the frame unwinds
                del out, arena_in
            st["d2h_bytes"] += out_arena.nbytes

        with TraceAnnotation("dmo.gather", call=call):
            if bplan is not None:
                outs = self._gather_block_outputs(bplan, graph, out_arena)
            else:
                outs = {}
                for t in graph.tensors:
                    if t.kind == "output":
                        s, off = t.storage(), plan.offsets[t.storage()]
                        outs[t.name] = out_arena[off:off + s.nbytes].view(
                            X.arena_dtype(s.dtype_bytes)).reshape(
                                X.tensor_shape(t))
        st["images"] += graph.batch
        if built:
            self._programs.add(fn)
            st["programs_built"] += 1
            st["first_call_s"] += time.perf_counter() - t0
        return outs

    @staticmethod
    def _weight_list(plan: Plan, weights, quant) -> List[np.ndarray]:
        """The program's weight arguments, in order. The order mirrors the
        per-image spec/stage expansion exactly: a batched op repeats its
        filter per image (one device buffer, no copies); a batched fused
        chain's stages run op-major so each weighted member's filter
        repeats per image consecutively."""
        from repro.kernels import arena_ops

        def w_of(op):
            if quant is not None and id(op) in quant.weights_q:
                return np.asarray(quant.weights_q[id(op)]["filter"], np.int8)
            return np.asarray(weights[id(op)]["filter"], np.float32)

        wflat: List[np.ndarray] = []
        wchains = _fused_chains(plan.order)
        wemitted: set = set()
        for op in plan.order:
            if op.kind == "reshape":
                continue
            cname = op.params.get("fuse_chain")
            if cname is not None:
                if cname in wemitted:
                    continue
                wemitted.add(cname)
                for m in wchains[cname]:
                    if m.kind in arena_ops.WEIGHTED_KINDS:
                        wflat.extend(
                            w_of(m)
                            for _ in range(m.output.storage().batch))
                continue
            if op.kind in arena_ops.WEIGHTED_KINDS:
                wflat.extend(w_of(op)
                             for _ in range(op.output.storage().batch))
        return wflat

    def _specs(self, plan: Plan, bplan: Optional[BlockPlan], quant) -> Tuple:
        """The lowered spec sequence of this call's route, its launches by
        kind (``(("launches.<kind>", n), ...)``) and the arena bytes its
        row-blocked launches stage between HBM and VMEM, from the
        per-executor cache when the same plan and quantisation ran
        before."""
        from repro.kernels import arena_ops
        route = (("stream" if self.mode == "streaming" else "blocks")
                 if bplan is not None else "flat")
        key = (id(plan), route, id(quant) if quant is not None else None)
        cached = self._lowered.get(key)
        if cached is not None and cached[0] is plan and cached[1] is quant:
            self._stats["lowering_hits"] += 1
            return cached[2:]
        self._stats["lowering_misses"] += 1
        if route == "stream":
            specs = self.lower_stream(bplan, quant)
        elif route == "blocks":
            specs = self.lower_blocks(bplan, quant)
        else:
            specs = self.lower(plan, quant)
        launches = tuple(collections.Counter(
            "launches." + s.kind for s in specs).items())
        stage_bytes = (sum(arena_ops.block_stage_bytes(s, bplan.total_rows)
                           for s in specs) if route == "blocks" else 0)
        self._lowered[key] = (plan, quant, specs, launches, stage_bytes)
        while len(self._lowered) > 32:
            self._lowered.popitem(last=False)
        return specs, launches, stage_bytes

    def _check_vmem(self, bplan: BlockPlan, graph, specs, wflat,
                    budget: int) -> None:
        """Refuse a row-blocked program whose VMEM residency exceeds
        ``budget``: the live window in streaming mode; in compiled mode
        the whole arena, plus a fused chain's scratch and the weights the
        launch stages there."""
        from repro.kernels import arena_ops
        if self.mode == "streaming":
            ws = bplan.window_schedule()
            if ws.max_resident_bytes > budget:
                raise ValueError(
                    f"streaming window of {graph.name!r} does not fit "
                    f"VMEM: peak resident {ws.max_resident_bytes} bytes "
                    f"({ws.max_window_rows} live rows) exceeds the "
                    f"{budget}-byte budget")
        elif self.mode == "compiled":
            need, rows, wbytes = 0, 0, 0
            i = 0
            for s in specs:
                nw = arena_ops.spec_weight_count(s)
                w = sum(int(x.nbytes) for x in wflat[i:i + nw])
                i += nw
                r = bplan.total_rows + s.scratch_rows
                if r * bplan.row_bytes + w > need:
                    need, rows, wbytes = r * bplan.row_bytes + w, r, w
            if need > budget:
                raise ValueError(
                    f"arena of {graph.name!r} does not fit VMEM: a "
                    f"launch needs {need} bytes ({rows} arena + scratch "
                    f"rows, {wbytes} weight bytes), over the "
                    f"{budget}-byte budget — mode='streaming' keeps "
                    "only the live window resident")

    @staticmethod
    def _seed_block_arena(bplan: BlockPlan, graph, inputs) -> np.ndarray:
        """A zeroed (total_rows, rowlen) typed arena with every model input
        scattered into its block layout (row-major over the used row
        prefix). Batched inputs scatter image by image: image ``b`` fills
        its own per-image-padded sub-block of ``image_rows`` rows."""
        dt = X.arena_dtype(bplan.dtype_bytes)
        L = bplan.arena_rowlen
        arena = np.zeros((bplan.total_rows, L), dt)
        for t in graph.tensors:
            if t.kind != "input":
                continue
            lay = bplan.layout_of(t)
            ir = lay.image_rows
            imgs = np.asarray(inputs[t.name], dt).reshape(lay.batch, -1)
            k = lay.row_span
            for b in range(lay.batch):
                off = lay.row_offset + b * ir
                flat = imgs[b]
                if k > 1:
                    # one image row spans k arena rows, column-padded per row
                    rl, h = lay.image_rowlen, ir // k
                    block = np.zeros((h, k * L), dt)
                    block[:, :rl] = flat.reshape(h, rl)
                    arena[off:off + ir, :] = block.reshape(ir, L)
                    continue
                block = np.zeros(ir * lay.rowlen, dt)
                block[:flat.size] = flat
                arena[off:off + ir, :lay.rowlen] = \
                    block.reshape(ir, lay.rowlen)
        return arena

    @staticmethod
    def _gather_block_outputs(bplan: BlockPlan, graph,
                              out_arena: np.ndarray) -> Dict[str, np.ndarray]:
        outs: Dict[str, np.ndarray] = {}
        L = bplan.arena_rowlen
        for t in graph.tensors:
            if t.kind != "output":
                continue
            lay = bplan.layout_of(t)
            k = lay.row_span
            ir = lay.image_rows
            imgs = []
            for b in range(lay.batch):
                off = lay.row_offset + b * ir
                if k > 1:
                    rl, h = lay.image_rowlen, ir // k
                    rows = out_arena[off:off + ir, :]
                    flat = rows.reshape(h, k * L)[:, :rl]
                else:
                    flat = out_arena[off:off + ir, :lay.rowlen]
                imgs.append(flat.reshape(-1)[:t.image_elems])
            outs[t.name] = np.stack(imgs).reshape(X.tensor_shape(t))
        return outs
