"""Generalised DMO arena kernels: every supported op as a Pallas call over
ONE shared arena buffer.

This generalises :mod:`repro.kernels.dmo_arena_dwconv` (a single hard-coded
depthwise conv) to the full op set a :class:`~repro.core.planner.Plan` can
contain: conv2d / depthwise_conv2d / pool / elementwise / softmax /
fully_connected / matmul / concat / pad / mean. Each op becomes one
``pl.pallas_call`` whose first operand is the shared arena and whose output
*aliases* it (``input_output_aliases={0: 0}``), so the arena is threaded
in-place through the op sequence — the TPU-VMEM analogue of the paper's SRAM
tensor arena.

The kernel bodies are written once against a small memory access layer,
and three kernels run them (an :class:`OpSpec` with ``rowlen == 0``
selects the flat program, ``rowlen > 0`` the row-blocked launches, and
``win_starts`` on top of that the roll grid):

- **flat** (:class:`_FlatMem`, :func:`_flat_kernel`) — the arena is a 1-D
  *byte* buffer; operands live at byte offsets and kernels bitcast their
  windows to the tier the spec declares (f32 windows / int8 bytes, the
  quantised tier running int32 accumulation plus the float32 requantisation
  of :mod:`repro.core.exec.ops`). Mixed-dtype plans execute in one buffer,
  but byte-granular dynamic slices fight the TPU's (8, 128)/(32, 128) VMEM
  tilings — this program is interpret-only.
- **staged** (:class:`_BlockMem`, :func:`_block_kernel`) — the arena is a
  2-D ``(rows, rowlen)`` buffer *typed* to the plan's dtype, laid out by
  :func:`repro.core.planner.legalise_for_blocks`: operands occupy whole
  arena rows at row-aligned offsets, conv/pool walk image rows via
  ``pl.ds`` on the row axis, and no bitcasts are needed — the program
  compiles through Mosaic. Packed layouts (spec ``in_addr``/``out_addr``
  triples) put ``cols_per_row`` narrow image rows in each arena row —
  reads pick the lane phase from static slices, writes merge it into the
  loaded row — or span one wide image row over ``row_span`` consecutive
  arena rows. The arena stays in HBM (``pl.ANY``); each launch copies the
  DMA-aligned spans of its operand blocks into one packed VMEM buffer
  (:func:`repro.core.planner.stage_rows`, every copy started before the
  first wait), runs the body, and copies back only its output block's
  span. A fused band chain is one such launch, its internal tensors in a
  second VMEM scratch buffer. Every launch of both routes but the roll
  grid's runs here.
- **roll grid** (:class:`_StreamRollMem`, :func:`_stream_roll_kernel`) —
  on the streaming route, conv / depthwise / pool run a row-tile grid: a
  double-buffered rolling input window (the tile-``t+1`` fetch is issued
  before the tile-``t`` wait; :class:`repro.core.planner.WindowSchedule`)
  plus an output slot mirroring the tile's DMA-aligned arena span. It is
  the one launch that holds less than a whole operand in VMEM.

Split row bands (§II.A) need no kernels of their own: a banded conv/pool's
spec carries its band shapes and its explicit band-local pads (a producer
band's leading row pad is *negative* — ``iy = oy*sh - ph + fy*dh`` simply
starts deeper in the full input), so the ordinary row kernels index exactly
the band's rows in both the flat and the row-blocked program.

Mosaic takes what the memory-access layer emits: values stay 2-D, taps are
static sublane shifts, int8 rows are addressed through aligned 8-row
windows, and every HBM <-> VMEM copy moves whole 8-row groups.

Safety contract (paper §III.A): kernels read *and* write through the aliased
output ref, and conv/pool walk output rows in ascending index order inside a
sequential ``fori_loop``. Reads for output row ``i`` therefore happen after
the row ``i-1`` store — exactly the element order the safe overlap ``O_s``
was derived against, which is why a planner-approved layout cannot clobber a
live value. Residual adds and channel concats of image tensors walk rows the
same way where their placement is safe in that order (:func:`_row_streamable`
checks it from the spec), and otherwise read every input before writing. In the blocked program a row store clobbers the *whole* arena
row (tiling padding included), which is why the legaliser re-derives each
diagonal distance at row granularity. A parallel grid over rows would break
the guarantee, precisely the paper's multi-threading caveat (§III.F) — keep
the row loop sequential.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.planner import (DMA_ROWS, Staging, stage_rows,
                                tile_writeback)

#: jnp mirrors of repro.core.exec.ops.ELEMENTWISE (same names, same maths).
_ELEMENTWISE = {
    "relu": lambda a: jnp.maximum(a, 0.0),
    "relu6": lambda a: jnp.clip(a, 0.0, 6.0),
    "sigmoid": lambda a: 1.0 / (1.0 + jnp.exp(-a)),
    "identity": lambda a: a,
    "add": lambda a, b: a + b,
    "mul": lambda a, b: a * b,
    "sub": lambda a, b: a - b,
}

#: Op kinds that carry one synthesized weight operand.
WEIGHTED_KINDS = frozenset({"conv2d", "depthwise_conv2d", "fully_connected"})


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Hashable, fully static description of one lowered op: operand
    placements in the shared arena, shapes, the arena dtype tier ("f32" or
    "i8"), and kind-specific parameters (plus quantisation statics for int8
    ops). Two plans with identical layouts produce equal specs, so lowered
    programs are shared.

    ``rowlen == 0`` selects the flat byte program: ``in_off``/``out_off``
    are *byte* offsets into a 1-D uint8 arena. ``rowlen > 0`` selects the
    row-blocked launches over a typed ``(rows, rowlen)`` arena: offsets are
    arena *row* indices and ``in_rows``/``out_rows`` carry each operand's
    ``(rows, used-elements-per-row)`` block shape from its
    :class:`~repro.core.planner.BlockLayout`. A non-empty ``win_starts``
    (on top of ``rowlen > 0``) selects the roll grid of a conv / depthwise
    / pool: the planner's per-output-tile fetch start table, with
    ``win_rows`` the op's live-window rows (rolling input window plus one
    output tile)."""

    kind: str
    in_off: Tuple[int, ...]            # byte (flat) | arena-row (blocked)
    in_shape: Tuple[Tuple[int, ...], ...]
    out_off: int
    out_shape: Tuple[int, ...]
    dtype: str = "f32"                 # arena tier: "f32" | "i8"
    meta: Tuple = ()                   # kind-specific statics (see builders)
    qmeta: Tuple = ()                  # int8 statics (zero points, multipliers)
    rowlen: int = 0                    # arena row elements (0 = flat program)
    in_rows: Tuple[Tuple[int, int], ...] = ()  # (rows, used) per input
    out_rows: Tuple[int, int] = ()             # (rows, used) of the output
    win_rows: int = 0                  # roll-grid live-window rows
    win_starts: Tuple[int, ...] = ()   # rolling-window fetch starts per tile
    #: Packed row addressing (blocked/streaming programs only): per-operand
    #: ``(cols_per_row, row_span, image_rowlen)`` triples from the packed
    #: :class:`~repro.core.planner.BlockLayout` geometry. Empty = the legacy
    #: one-image-row-per-arena-row addressing (and bit-identical specs for
    #: legacy plans). ``out_tile`` is the *image* rows one streaming grid
    #: tile computes (0 = the dtype sublane, the legacy tiling).
    in_addr: Tuple[Tuple[int, int, int], ...] = ()
    out_addr: Tuple[int, int, int] = ()
    out_tile: int = 0
    #: Fused band-chain super-kernel (``kind == "fused"``): the chain's
    #: member ops in graph order as nested stage specs. Stage offsets whose
    #: ``in_scratch``/``out_scratch`` flag is set are *scratch-local* slot
    #: offsets (rows blocked / bytes flat, packed by
    #: :func:`repro.core.planner.fused_slots`); chain-internal tensors
    #: therefore never touch the arena — one ``pallas_call`` runs the whole
    #: chain with its halos resident in VMEM and only the terminal stage
    #: (the reassembling concat) writes back at the planned offset.
    stages: Tuple["OpSpec", ...] = ()
    scratch_rows: int = 0              # chain scratch: rows (blocked) | bytes (flat)
    in_scratch: Tuple[int, ...] = ()   # stage flag: input i reads the scratch ref
    out_scratch: int = 0               # stage flag: output writes the scratch ref
    #: The graph op (or fused chain) the spec lowers: names its kernel.
    name: str = ""


def _elems(shape: Tuple[int, ...]) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def _isz(dtype: str) -> int:
    return 1 if dtype == "i8" else 4


def _jnp_dtype(dtype: str):
    return jnp.int8 if dtype == "i8" else jnp.float32


def _sub(dtype: str) -> int:
    """Sublane tile rows for the arena dtype (mirrors planner.TPU_TILES)."""
    return 32 if dtype == "i8" else 8


def _addr_in(spec: OpSpec, i: int) -> Tuple[int, int, int]:
    """Input ``i``'s packed addressing triple ((1, 1, 0) = legacy)."""
    return spec.in_addr[i] if spec.in_addr else (1, 1, 0)


def _addr_out(spec: OpSpec) -> Tuple[int, int, int]:
    return spec.out_addr if spec.out_addr else (1, 1, 0)


def kernel_name(spec: OpSpec) -> str:
    """The ``pallas_call`` name of a spec's kernel: ``dmo_<kind>_<op>``
    (``dmo_concat_m35_3_cat``), so that a device trace tells the kernels
    of joins, pools and convs apart."""
    op = "".join(ch if ch.isalnum() else "_" for ch in spec.name)
    return f"dmo_{spec.kind}_{op}" if op else f"dmo_{spec.kind}"


def _tile_geom(spec: OpSpec) -> Tuple[int, int]:
    """(image rows, sublane-rounded arena rows) of one streaming output
    tile — mirrors planner.tile_rows/tile_arena_rows (``out_tile`` is a
    multiple of ``cols_per_row``, so lane phases complete within a tile)."""
    sub = _sub(spec.dtype)
    tr = spec.out_tile or sub
    c, k, _ = _addr_out(spec)
    ar = (tr - 1) // c + 1 if c > 1 else tr * k
    return tr, -(-ar // sub) * sub


# ---------------------------------------------------------------------------
# Value relayouts. Kernel bodies see 2-D values only — an image row as a
# (1, W*C) lane vector, a whole tensor as an (N, C) matrix over its last
# axis — and these helpers move between the two with static lane/sublane
# slices and concatenates, the relayouts Mosaic lowers (it refuses lane ->
# sublane reshapes of int8-tier values, and dynamic slices and gathers of
# loaded values outright).
# ---------------------------------------------------------------------------


_HIGHEST = jax.lax.Precision.HIGHEST


def _cdt(dtype: str):
    """Compute dtype of an arena tier: int8 values travel as int32."""
    return jnp.int32 if dtype == "i8" else jnp.float32


def _mat_shape(shape: Tuple[int, ...]) -> Tuple[int, int]:
    """(N, C) matrix view of a tensor over its last axis."""
    c = int(shape[-1]) if shape else 1
    return _elems(shape) // c, c


def _cat(parts, axis: int):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _widen(v, width: int):
    """A (1, w) lane vector cut or zero-filled to (1, width)."""
    w = v.shape[1]
    if w >= width:
        return v[:, :width]
    return jnp.concatenate([v, jnp.zeros((1, width - w), v.dtype)], axis=1)


def _unflatten(v, n: int, c: int):
    """The first ``n*c`` lanes of a (1, >= n*c) vector as an (n, c)
    matrix, row ``j`` = lanes ``[j*c, (j+1)*c)``."""
    return _cat([v[:, j * c:(j + 1) * c] for j in range(n)], 0)


def _flatten(x):
    """Inverse of :func:`_unflatten`: an (n, c) matrix as (1, n*c)."""
    return _cat([x[j:j + 1, :] for j in range(x.shape[0])], 1)


def _pixel_phases(row, w: int, c: int, s: int):
    """A (1, w*c) image row split into ``s`` stride phases: phase ``q`` is
    the (ceil((w-q)/s), c) matrix of pixels ``q, q+s, q+2s, ...``."""
    if s == 1:
        return [_unflatten(row, w, c)]
    return [_cat([row[:, x * c:(x + 1) * c] for x in range(q, w, s)], 0)
            for q in range(min(s, w))]


def _taps(phases, s: int, off: int, n: int, c: int, dt):
    """The (n, c) tap matrix whose row ``o`` is pixel ``o*s + off`` —
    zero where that pixel lies outside the row. One static sublane shift of
    the matching stride phase, in place of a per-tap gather."""
    q, m = off % s, off // s
    if q >= len(phases):
        return jnp.zeros((n, c), dt)
    p = phases[q]
    lo, hi = max(0, -m), min(n, p.shape[0] - m)
    if hi <= lo:
        return jnp.zeros((n, c), dt)
    parts = [p[lo + m:hi + m]]
    if lo:
        parts.insert(0, jnp.zeros((lo, c), dt))
    if hi < n:
        parts.append(jnp.zeros((n - hi, c), dt))
    return _cat(parts, 0)


def _pick(sel, options):
    """``options[sel]`` for a static or traced index (select chain)."""
    if isinstance(sel, int):
        return options[sel]
    out = options[0]
    for p in range(1, len(options)):
        out = jnp.where(sel == p, options[p], out)
    return out


# ---------------------------------------------------------------------------
# Memory access layer: the one place the arena addressings differ. Kernel
# bodies below are written once against this API.
# ---------------------------------------------------------------------------


def _aligned8(r):
    if isinstance(r, int):
        return r // 8 * 8
    return pl.multiple_of(r // 8 * 8, 8)


def _row_load(ref, r):
    """Arena row ``r`` (static or traced) of a (rows, L) ref as a (1, L)
    value in the compute dtype. Int8 rows pack four to a 32-bit sublane, so
    Mosaic addresses them in aligned 8-row windows: load the window holding
    ``r`` and keep its row."""
    if ref.dtype == jnp.int8:
        base = _aligned8(r)
        win = ref[pl.ds(base, 8), :].astype(jnp.int32)
        sel = jax.lax.broadcasted_iota(jnp.int32, win.shape, 0) == r - base
        return jnp.sum(jnp.where(sel, win, 0), axis=0, keepdims=True)
    return ref[pl.ds(r, 1), :]


def _row_store(ref, r, row) -> None:
    """Overwrite arena row ``r`` with a (1, L) compute-dtype value (int8:
    read-modify-write of the aligned 8-row window; the other rows are
    written back unchanged, and the row loops are sequential)."""
    if ref.dtype == jnp.int8:
        base = _aligned8(r)
        win = ref[pl.ds(base, 8), :].astype(jnp.int32)
        sel = jax.lax.broadcasted_iota(jnp.int32, win.shape, 0) == r - base
        ref[pl.ds(base, 8), :] = jnp.where(sel, row, win).astype(jnp.int8)
    else:
        ref[pl.ds(r, 1), :] = row.astype(ref.dtype)


def _image_row(ref, row0, iy, used: int, addr: Tuple[int, int, int]):
    """One image row (``used`` elements, as (1, used)) of an operand whose
    image row 0 starts at arena row ``row0``, at row index ``iy``. Packed
    rows live at lane phase ``(iy % c) * rl`` of arena row ``iy // c``; a
    spanning image row occupies ``k`` consecutive arena rows."""
    c, k, rl = addr
    if c > 1:
        row = _row_load(ref, row0 + iy // c)
        return _pick(iy % c, [row[:, p * rl:(p + 1) * rl] for p in range(c)])
    if k > 1:
        return _cat([_row_load(ref, row0 + iy * k + j) for j in range(k)],
                    1)[:, :used]
    return _row_load(ref, row0 + iy)[:, :used]


def _put_image_row(ref, row0, oy, L: int, addr: Tuple[int, int, int],
                   val) -> None:
    """Store a (1, rl) image row at row index ``oy``. A packed row store is
    a read-modify-write of its arena row (the other lane phases must
    survive); safe because the row loop is sequential and the planner's
    O_s is derived at whole-arena-row granularity, phases included. Legacy
    and spanning rows clobber their whole arena rows, tile padding
    zero-filled."""
    c, k, rl = addr
    if c > 1:
        r = row0 + oy // c
        ph = oy % c
        old = _row_load(ref, r)
        lane = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1)
        new = old
        for p in range(c):
            if isinstance(ph, int) and ph != p:
                continue
            placed = _widen(_cat([jnp.zeros((1, p * rl), val.dtype), val]
                                 if p else [val], 1), L)
            hit = (lane >= p * rl) & (lane < (p + 1) * rl)
            if not isinstance(ph, int):
                hit = hit & (ph == p)
            new = jnp.where(hit, placed, new)
        _row_store(ref, r, new)
    elif k > 1:
        wide = _widen(val, k * L)
        for j in range(k):
            _row_store(ref, row0 + oy * k + j, wide[:, j * L:(j + 1) * L])
    else:
        _row_store(ref, row0 + oy, _widen(val, L))


def _is_image(shape, rows: int, used: int, addr: Tuple[int, int, int]):
    """Does an operand block keep image-row structure (so its rows can be
    addressed one image row at a time)? Dense blocks pack the flat tensor
    over full arena rows."""
    if len(shape) < 3:
        return False
    h, rl = int(shape[-3]), _elems(shape[-2:])
    c, k, _ = addr
    if c > 1:
        return addr[2] == rl and rows == -(-h // c)
    if k > 1:
        return addr[2] == rl and rows == h * k
    return used == rl and rows == h


def _block_matrix(ref, off, rows: int, used: int,
                  addr: Tuple[int, int, int], shape):
    """A whole operand block as its (N, C) matrix: the used prefix of each
    arena row (packing is row-major in image order; spanning rows carry
    per-image-row padding that is stripped) joined into the flat element
    stream, then cut into rows of the last axis."""
    n, c = _mat_shape(shape)
    _, k, rl = addr
    if k > 1:
        parts = [_cat([_row_load(ref, off + y * k + j) for j in range(k)],
                      1)[:, :rl] for y in range(rows // k)]
    else:
        parts = [_row_load(ref, off + r)[:, :used] for r in range(rows)]
    return _unflatten(_cat(parts, 1), n, c)


def _put_block_matrix(ref, off, rows: int, used: int, L: int,
                      addr: Tuple[int, int, int], val) -> None:
    """Inverse of :func:`_block_matrix`: write an (N, C) matrix as a padded
    (rows, L) block (dense tail and per-row tile padding zero-filled)."""
    stream = _flatten(val)
    _, k, rl = addr
    if k > 1:
        for y in range(rows // k):
            seg = _widen(stream[:, y * rl:(y + 1) * rl], k * L)
            for j in range(k):
                _row_store(ref, off + y * k + j, seg[:, j * L:(j + 1) * L])
        return
    for r in range(rows):
        seg = stream[:, r * used:(r + 1) * used]
        _row_store(ref, off + r, _widen(seg, L) if seg.shape[1]
                   else jnp.zeros((1, L), stream.dtype))


class _FlatMem:
    """Flat byte-arena accessor: bitcast typed windows at byte offsets.

    Per-operand refs resolve through ``_in_ref``/``_out_ref`` (the arena ref
    for plain ops); the fused-chain subclasses override them to route
    scratch-flagged operands to the chain's VMEM scratch buffer."""

    def __init__(self, ref, spec: OpSpec):
        self.ref, self.spec = ref, spec
        self.isz = _isz(spec.dtype)
        self.cdt = _cdt(spec.dtype)

    def _in_ref(self, i: int):
        return self.ref

    def _out_ref(self):
        return self.ref

    def _read(self, ref, byte_off, elems: int):
        if self.spec.dtype == "i8":
            raw = ref[pl.dslice(byte_off, elems)]
            v = jax.lax.bitcast_convert_type(raw, jnp.int8)
        else:
            raw = ref[pl.dslice(byte_off, 4 * elems)].reshape(elems, 4)
            v = jax.lax.bitcast_convert_type(raw, jnp.float32)
        return v.astype(self.cdt).reshape(1, elems)

    def is_image(self, i: int) -> bool:
        return len(self.spec.in_shape[i]) >= 3

    def out_is_image(self) -> bool:
        return len(self.spec.out_shape) >= 3

    def row_span(self, i: Optional[int], y: int) -> Tuple[int, int]:
        """The bytes ``[lo, hi)`` image row ``y`` of input ``i`` (the
        output for ``None``) occupies."""
        shape = self.spec.out_shape if i is None else self.spec.in_shape[i]
        off = self.spec.out_off if i is None else self.spec.in_off[i]
        row = _elems(shape[-2:]) * self.isz
        return off + y * row, off + (y + 1) * row

    def read_t(self, i: int):
        """Input ``i`` as its (N, C) matrix."""
        shape = self.spec.in_shape[i]
        n, c = _mat_shape(shape)
        return self._read(self._in_ref(i), self.spec.in_off[i],
                          n * c).reshape(n, c)

    def read_row(self, i: int, iy):
        """One image row (1, W*C) of input ``i`` at a row index."""
        row = _elems(self.spec.in_shape[i][-2:])
        return self._read(self._in_ref(i),
                          self.spec.in_off[i] + iy * row * self.isz, row)

    def _write(self, ref, byte_off, value):
        flat = value.reshape(-1).astype(_jnp_dtype(self.spec.dtype))
        raw = jax.lax.bitcast_convert_type(flat, jnp.uint8).reshape(-1)
        ref[pl.dslice(byte_off, raw.size)] = raw

    def write(self, value):
        self._write(self._out_ref(), self.spec.out_off, value)

    def write_row(self, oy, value):
        row = _elems(self.spec.out_shape[-2:])
        self._write(self._out_ref(),
                    self.spec.out_off + oy * row * self.isz, value)

    def fori_rows(self, oh: int, body) -> None:
        """Sequential walk over every output row (§III.F: keep it serial)."""
        jax.lax.fori_loop(0, oh, body, 0)


class _BlockMem:
    """Row-blocked accessor over a typed (R, L) ref: image rows by arena
    row (static or traced) and lane phase, whole blocks as matrices — no
    bitcasts, compiled-mode lowerable. ``at`` maps an operand's arena row
    to its row of ``ref`` (a staged launch's
    :meth:`repro.core.planner.Staging.row`; the identity by default)."""

    def __init__(self, ref, spec: OpSpec, at=None):
        self.ref, self.spec = ref, spec
        self.cdt = _cdt(spec.dtype)
        self.L = spec.rowlen
        self._at = at or (lambda r: r)

    def _in_ref(self, i: int):
        return self.ref

    def _out_ref(self):
        return self.ref

    def _in_off(self, i: int):
        return self._at(self.spec.in_off[i])

    def _out_off(self):
        return self._at(self.spec.out_off)

    def is_image(self, i: int) -> bool:
        rows, used = self.spec.in_rows[i]
        return _is_image(self.spec.in_shape[i], rows, used,
                         _addr_in(self.spec, i))

    def out_is_image(self) -> bool:
        rows, used = self.spec.out_rows
        return _is_image(self.spec.out_shape, rows, used,
                         _addr_out(self.spec))

    def row_span(self, i: Optional[int], y: int) -> Tuple[int, int]:
        """The arena rows ``[lo, hi)`` image row ``y`` of input ``i`` (the
        output for ``None``) touches: a packed row's whole arena row, a
        spanning row's ``row_span`` rows."""
        off = self._out_off() if i is None else self._in_off(i)
        c, k, _ = _addr_out(self.spec) if i is None else _addr_in(
            self.spec, i)
        if c > 1:
            return off + y // c, off + y // c + 1
        return off + y * k, off + (y + 1) * k

    def read_t(self, i: int):
        rows, used = self.spec.in_rows[i]
        return _block_matrix(self._in_ref(i), self._in_off(i), rows, used,
                             _addr_in(self.spec, i), self.spec.in_shape[i])

    def read_row(self, i: int, iy):
        used = _elems(self.spec.in_shape[i][-2:])
        return _image_row(self._in_ref(i), self._in_off(i), iy, used,
                          _addr_in(self.spec, i))

    def write(self, value):
        rows, used = self.spec.out_rows
        n, c = _mat_shape(self.spec.out_shape)
        _put_block_matrix(self._out_ref(), self._out_off(), rows, used,
                          self.L, _addr_out(self.spec),
                          value.astype(self.cdt).reshape(n, c))

    def write_row(self, oy, value):
        _put_image_row(self._out_ref(), self._out_off(), oy, self.L,
                       _addr_out(self.spec),
                       _flatten(value.astype(self.cdt)))

    def fori_rows(self, oh: int, body) -> None:
        jax.lax.fori_loop(0, oh, body, 0)


class _RoutedMem:
    """Mixin for fused-chain stages: each operand resolves to the arena ref
    or to the chain's VMEM scratch ref per the stage spec's
    ``in_scratch``/``out_scratch`` flags. Scratch-flagged offsets are
    scratch-local slot positions; arena-flagged ones the plan's placements —
    so the written-once bodies run unmodified while chain-internal values
    stay VMEM-resident."""

    def __init__(self, arena_ref, scratch_ref, spec: OpSpec, *args):
        super().__init__(arena_ref, spec, *args)
        self.scratch_ref = scratch_ref

    def _in_ref(self, i: int):
        flags = self.spec.in_scratch
        return self.scratch_ref if flags and flags[i] else self.ref

    def _out_ref(self):
        return self.scratch_ref if self.spec.out_scratch else self.ref


class _RoutedFlatMem(_RoutedMem, _FlatMem):
    pass


class _RoutedBlockMem(_RoutedMem, _BlockMem):
    """Scratch-flagged operands keep their scratch rows; arena-flagged ones
    map through ``at`` like any staged operand."""

    def _in_off(self, i: int):
        flags = self.spec.in_scratch
        if flags and flags[i]:
            return self.spec.in_off[i]
        return super()._in_off(i)

    def _out_off(self):
        if self.spec.out_scratch:
            return self.spec.out_off
        return super()._out_off()


class _StreamRollMem(_BlockMem):
    """Streaming accessor for one output-row tile of a rolling-window
    conv/pool: reads index the double-buffered VMEM input-window slot
    (arena row ``r`` lives at scratch row ``r - base``; reads that fall
    outside the window are the kernels' clamped+masked taps, clamped
    in-bounds here and discarded by the mask), writes land in the output
    slot, which mirrors arena rows from ``out_base`` on and is copied back
    whole after the tile. ``fori_rows`` restricts the shared kernel bodies
    to this tile's output rows — the bodies themselves stay written-once."""

    def __init__(self, in_ref, out_ref, spec: OpSpec, base, out_base,
                 row_lo, row_hi):
        super().__init__(in_ref, spec)
        self.out_ref, self.out_base = out_ref, out_base
        self.base, self.row_lo, self.row_hi = base, row_lo, row_hi

    def read_row(self, i: int, iy):
        used = _elems(self.spec.in_shape[i][-2:])
        c, k, _ = _addr_in(self.spec, i)
        # clamp the window-relative image row so every arena row it touches
        # lies inside the window slot
        last = self.ref.shape[0] - 1
        row0 = self.spec.in_off[i] - self.base
        if c > 1:
            lo, hi = -row0 * c, (last - row0) * c + c - 1
        else:
            lo, hi = -(row0 // k), (last - row0 - k + 1) // k
        return _image_row(self.ref, row0, jnp.clip(iy, lo, hi), used,
                          _addr_in(self.spec, i))

    def write_row(self, oy, value):
        # packed rows read-modify-write their slot row, so the lane phases
        # of one arena row accumulate across the tile's image rows
        _put_image_row(self.out_ref, self.spec.out_off - self.out_base, oy,
                       self.L, _addr_out(self.spec),
                       _flatten(value.astype(self.cdt)))

    def fori_rows(self, oh: int, body) -> None:
        jax.lax.fori_loop(self.row_lo, self.row_hi, body, 0)


def _requant(acc, mult: float, zp: int):
    """jnp mirror of repro.core.exec.ops.requantise (same f32 arithmetic);
    the int8-range result stays in the int32 compute dtype."""
    q = jnp.round(acc.astype(jnp.float32) * jnp.float32(mult)) + zp
    return jnp.clip(q, -128, 127).astype(jnp.int32)


def _dequant(x, scale: float, zp: int):
    return (x.astype(jnp.float32) - zp) * jnp.float32(scale)


def _quant(v, scale: float, zp: int):
    q = jnp.round(v / jnp.float32(scale)) + zp
    return jnp.clip(q, -128, 127).astype(jnp.int32)


def _dot(a, b):
    """f32 matrix product at full f32 precision (the MXU's default would
    round the operands to bf16). Float sums go through it too: a dot's
    summation order does not depend on how the compiler fuses its operands'
    producers, so every arena program rounds alike."""
    return jnp.dot(a, b, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _qdot(a, b, a_zp: int, b_zp: int):
    """Exact ``(a - a_zp) @ (b - b_zp)`` over int8-range int32 operands, as
    one int8 x int8 -> int32 product plus zero-point corrections (the MXU
    multiplies int8, not int32)."""
    acc = jnp.dot(a.astype(jnp.int8), b.astype(jnp.int8),
                  preferred_element_type=jnp.int32)
    if a_zp:
        acc = acc - a_zp * jnp.sum(b, axis=0, keepdims=True)
    if b_zp:
        acc = acc - b_zp * jnp.sum(a, axis=1, keepdims=True)
        acc = acc + a.shape[1] * a_zp * b_zp
    return acc


# ---------------------------------------------------------------------------
# Kernel bodies — all state lives in the aliased arena (or its staged VMEM
# rows); the input operand only seeds the initial contents via the alias.
# Bodies are addressing-agnostic: every arena touch goes through the mem
# layer, so the flat, staged and roll-grid kernels share them.
# ---------------------------------------------------------------------------


def _conv_kernel(mem, w_ref, *, spec: OpSpec):
    ih, iw, ic = spec.in_shape[0][-3:]
    oh, ow, oc = spec.out_shape[-3:]
    kh, kw, sh, sw, dh, dw, ph, pw, mult = spec.meta
    depthwise = spec.kind == "depthwise_conv2d"
    quant = spec.dtype == "i8"
    cdt = _cdt(spec.dtype)
    x_zp, amult, y_zp = spec.qmeta if quant else (0, 1.0, 0)
    if quant and not depthwise:
        # int8 MXU products of the raw taps; padding taps carry x_zp, so
        # one correction per output channel removes every zero point
        wsum = sum(jnp.sum(w_ref[fy, fx].astype(jnp.int32), axis=0,
                           keepdims=True)
                   for fy in range(kh) for fx in range(kw))

    def body(oy, _):
        acc = jnp.zeros((ow, oc), cdt)
        for fy in range(kh):                    # static unroll (kh small)
            iy = oy * sh - ph + fy * dh
            row_ok = (iy >= 0) & (iy < ih)
            iy_c = jnp.clip(iy, 0, ih - 1)
            phases = _pixel_phases(mem.read_row(0, iy_c), iw, ic, sw)
            for fx in range(kw):
                ix = jax.lax.broadcasted_iota(jnp.int32, (ow, 1), 0)
                ix = ix * sw - pw + fx * dw
                valid = (ix >= 0) & (ix < iw) & row_ok
                taps = _taps(phases, sw, fx * dw - pw, ow, ic, cdt)
                w = w_ref[fy, fx]
                if depthwise:
                    if mult > 1:
                        taps = jnp.repeat(taps, mult, axis=1)
                    # the select sits between product and sum, so no
                    # compiler fuses them into one FMA: every program
                    # rounds each tap alike
                    acc += jnp.where(valid, (taps - x_zp) * w.astype(cdt), 0)
                    continue
                taps = jnp.where(valid, taps, x_zp)    # (ow, ic)
                if quant:
                    acc += jnp.dot(taps.astype(jnp.int8), w,
                                   preferred_element_type=jnp.int32)
                else:
                    acc += _dot(taps, w)
        if quant and not depthwise:
            acc = acc - x_zp * wsum
        mem.write_row(oy, _requant(acc, amult, y_zp) if quant else acc)
        return 0

    mem.fori_rows(oh, body)


def _pool_kernel(mem, *, spec: OpSpec):
    ih, iw, c = spec.in_shape[0][-3:]
    oh, ow, _ = spec.out_shape[-3:]
    kh, kw, sh, sw, ph, pw, mode = spec.meta
    quant = spec.dtype == "i8"
    cdt = _cdt(spec.dtype)

    def body(oy, _):
        if quant:
            acc = jnp.full((ow, c), -2147483647 if mode == "max" else 0,
                           jnp.int32)
        else:
            acc = jnp.full((ow, c), -jnp.inf if mode == "max" else 0.0,
                           jnp.float32)
        cnt = jnp.zeros((ow, 1), jnp.float32)
        for fy in range(kh):
            iy = oy * sh - ph + fy
            row_ok = (iy >= 0) & (iy < ih)
            iy_c = jnp.clip(iy, 0, ih - 1)
            phases = _pixel_phases(mem.read_row(0, iy_c), iw, c, sw)
            for fx in range(kw):
                ix = jax.lax.broadcasted_iota(jnp.int32, (ow, 1), 0)
                ix = ix * sw - pw + fx
                valid = (ix >= 0) & (ix < iw) & row_ok
                taps = _taps(phases, sw, fx - pw, ow, c, cdt)
                if mode == "max":
                    acc = jnp.where(valid, jnp.maximum(acc, taps), acc)
                else:
                    acc = acc + jnp.where(valid, taps, 0 if quant else 0.0)
                    cnt = cnt + valid.astype(jnp.float32)
        if quant:
            x_zp, amult, y_zp = spec.qmeta
            if mode == "avg":
                val = acc.astype(jnp.float32) / jnp.maximum(cnt, 1.0) - x_zp
            else:
                val = acc - x_zp
            out = _requant(val, amult, y_zp)
        else:
            out = acc / jnp.maximum(cnt, 1.0) if mode == "avg" else acc
        mem.write_row(oy, out)
        return 0

    mem.fori_rows(oh, body)


def _row_streamable(mem, spec: OpSpec) -> bool:
    """Can a join run one output row at a time: reading row ``y`` of every
    input, then writing output row ``y``, for ``y`` ascending? Every
    operand must keep image rows, and in place writing row ``y`` must
    leave every input row after ``y`` intact: the span it clobbers lies
    below the next input row or above the input's last. Where it does not,
    the whole-tensor body (every read before any write) runs instead."""
    n_in = len(spec.in_shape)
    if not (len(spec.out_shape) >= 3 and mem.out_is_image()
            and all(mem.is_image(i) for i in range(n_in))):
        return False
    oh = spec.out_shape[-3]
    for i in range(n_in):
        end = mem.row_span(i, spec.in_shape[i][-3] - 1)[1]
        for y in range(oh - 1):
            lo, hi = mem.row_span(None, y)
            if hi > mem.row_span(i, y + 1)[0] and lo < end:
                return False
    return True


def _elementwise_kernel(mem, *, spec: OpSpec):
    fn = _ELEMENTWISE[spec.meta[0]]
    n_in = len(spec.in_shape)

    def compute(xs):
        if spec.dtype == "i8":
            in_q, (ys, yzp) = spec.qmeta
            xs = [_dequant(x, s, zp) for x, (s, zp) in zip(xs, in_q)]
        v = fn(*xs).astype(jnp.float32)
        return _quant(v, ys, yzp) if spec.dtype == "i8" else v

    if all(s == spec.out_shape for s in spec.in_shape) and _row_streamable(
            mem, spec):
        # same-shape image operands (a residual add): one row per step
        def body(oy, _):
            mem.write_row(oy, compute([mem.read_row(i, oy)
                                       for i in range(n_in)]))
            return 0

        mem.fori_rows(spec.out_shape[-3], body)
        return
    xs = [mem.read_t(i) for i in range(n_in)]
    if len(xs) == 2 and xs[1].shape != xs[0].shape:
        # trailing-axis broadcast: the smaller operand's rows repeat
        reps = xs[0].shape[0] // xs[1].shape[0]
        xs[1] = (jnp.broadcast_to(xs[1], xs[0].shape) if xs[1].shape[0] == 1
                 else jnp.tile(xs[1], (reps, 1)))
    mem.write(compute(xs))


def _softmax_kernel(mem, *, spec: OpSpec):
    x = mem.read_t(0)
    if spec.dtype == "i8":
        (xs, xzp), (ys, yzp) = spec.qmeta
        x = _dequant(x, xs, xzp)
    e = jnp.exp(x - jnp.max(x, axis=-1, keepdims=True))
    y = e / _dot(e, jnp.ones((e.shape[1], 1), jnp.float32))
    mem.write(_quant(y, ys, yzp) if spec.dtype == "i8" else y)


def _fully_connected_kernel(mem, w_ref, *, spec: OpSpec):
    idim = spec.in_shape[0][-1]
    x = mem.read_t(0).reshape(-1, idim)
    if spec.dtype == "i8":
        x_zp, amult, y_zp = spec.qmeta
        acc = _qdot(x, w_ref[...].astype(jnp.int32), x_zp, 0)
        y = _requant(acc, amult, y_zp)
    else:
        y = _dot(x, w_ref[...])
    mem.write(y)


def _matmul_kernel(mem, *, spec: OpSpec):
    a = mem.read_t(0).reshape(-1, spec.in_shape[0][-1])
    b = mem.read_t(1)
    if spec.dtype == "i8":
        a_zp, b_zp, amult, y_zp = spec.qmeta
        y = _requant(_qdot(a, b, a_zp, b_zp), amult, y_zp)
    else:
        y = _dot(a, b)
    mem.write(y)


def _rescale(x, src, dst):
    """jnp mirror of repro.core.exec.ops.rescale_q (f32 multiplier is baked
    into qmeta by the lowering, so both backends use the identical bits)."""
    (s_zp, mult), (y_zp,) = src, dst
    return _requant(x.astype(jnp.int32) - s_zp, mult, y_zp)


def _concat_kernel(mem, *, spec: OpSpec):
    n_in = len(spec.in_shape)
    rank = len(spec.out_shape)
    axis = spec.meta[0] % rank
    if spec.dtype == "i8":
        in_q, (yzp,) = spec.qmeta
        fix = [functools.partial(_rescale, src=q, dst=(yzp,)) for q in in_q]
    else:
        fix = [lambda x: x] * n_in
    if rank >= 3 and axis == rank - 3 and mem.out_is_image() and all(
            mem.is_image(i) for i in range(n_in)):
        # image-row concat (split-band reassembly): copy rows in place
        y0 = 0
        for i in range(n_in):
            for y in range(spec.in_shape[i][-3]):
                mem.write_row(y0 + y, fix[i](mem.read_row(i, y)))
            y0 += spec.in_shape[i][-3]
        return
    if rank >= 3 and axis == rank - 1 and _row_streamable(mem, spec):
        # channel concat of image tensors: each output row interleaves the
        # inputs' pixels, one row per step
        w = spec.out_shape[-2]
        chans = [s[-1] for s in spec.in_shape]

        def body(oy, _):
            rows = [fix[i](mem.read_row(i, oy)) for i in range(n_in)]
            mem.write_row(oy, _cat([r[:, x * c:(x + 1) * c]
                                    for x in range(w)
                                    for r, c in zip(rows, chans)], 1))
            return 0

        mem.fori_rows(spec.out_shape[-3], body)
        return
    xs = [fix[i](mem.read_t(i)) for i in range(n_in)]
    if axis in (0, rank - 1):
        # the leading axis joins flat streams, the last axis the matrices'
        # columns
        mem.write(jnp.concatenate(xs, axis=0 if axis == 0 else 1))
        return
    xs = [x.reshape(s) for x, s in zip(xs, spec.in_shape)]
    mem.write(jnp.concatenate(xs, axis=axis))


def _pad_kernel(mem, *, spec: OpSpec):
    x = mem.read_t(0).reshape(spec.in_shape[0])
    if spec.dtype == "i8":
        (x_zp, mult), (y_zp,) = spec.qmeta
        padded = jnp.pad(x, spec.meta[0], constant_values=x_zp)
        mem.write(_rescale(padded, (x_zp, mult), (y_zp,)))
        return
    mem.write(jnp.pad(x, spec.meta[0]))


def _mean_kernel(mem, *, spec: OpSpec):
    x = mem.read_t(0)
    shape = spec.in_shape[0]
    axes = tuple(a % len(shape) for a in spec.meta[0])
    lead = axes == tuple(range(len(shape) - 1))
    if not lead:
        x = x.reshape(shape)
    cnt = 1
    for ax in axes:
        cnt *= shape[ax]
    if spec.dtype == "i8":
        x_zp, amult, y_zp = spec.qmeta
        acc = (jnp.sum(x, axis=0, keepdims=True) if lead
               else jnp.sum(x, axis=axes))
        val = acc.astype(jnp.float32) / jnp.float32(cnt) - x_zp
        y = _requant(val, amult, y_zp)
    elif lead:
        y = _dot(jnp.ones((1, x.shape[0]), jnp.float32), x) / jnp.float32(cnt)
    else:
        y = jnp.mean(x, axis=axes)
    mem.write(y)


_BODIES = {
    "conv2d": _conv_kernel,
    "depthwise_conv2d": _conv_kernel,
    "pool": _pool_kernel,
    "elementwise": _elementwise_kernel,
    "softmax": _softmax_kernel,
    "fully_connected": _fully_connected_kernel,
    "matmul": _matmul_kernel,
    "concat": _concat_kernel,
    "pad": _pad_kernel,
    "mean": _mean_kernel,
}


def spec_weight_count(spec: OpSpec) -> int:
    """Weight operands a lowered spec consumes (a fused chain consumes all
    of its stages' weights, in stage order)."""
    if spec.kind == "fused":
        return sum(1 for st in spec.stages if st.kind in WEIGHTED_KINDS)
    return 1 if spec.kind in WEIGHTED_KINDS else 0


def _run_stages(spec: OpSpec, mem_of, w_refs) -> None:
    """Fused band chain: run the stages in graph order, each against the
    accessor ``mem_of(stage)``. Chain-internal operands route to the VMEM
    scratch per the stage flags, so intermediate bands and their halo rows
    never touch the arena — only the terminal stage (the reassembling
    concat) writes at the planned offset. Stage order is the graph order,
    so every read of the chain input precedes the terminal write: the
    planner may overlap the chain's input and output via plain disjoint
    liveness."""
    wi = 0
    for st in spec.stages:
        nw = spec_weight_count(st)
        _BODIES[st.kind](mem_of(st), *w_refs[wi:wi + nw], spec=st)
        wi += nw


def _flat_kernel(*refs, spec: OpSpec):
    """Flat byte program (interpret mode only): refs are (arena_in,
    *weights, arena_out[, chain scratch]); the body reads and writes
    through the aliased output ref, which the interpreter hands the
    arena's contents."""
    nw = spec_weight_count(spec)
    w_refs, o_ref = refs[1:1 + nw], refs[1 + nw]
    if spec.kind == "fused":
        scratch = refs[2 + nw]
        _run_stages(spec, lambda st: _RoutedFlatMem(o_ref, scratch, st),
                    w_refs)
    else:
        _BODIES[spec.kind](_FlatMem(o_ref, spec), *w_refs, spec=spec)


def block_stage_spans(spec: OpSpec, total_rows: int) -> Staging:
    """The staging of one row-blocked launch over an arena of
    ``total_rows`` rows (:func:`repro.core.planner.stage_rows` over its
    input and output blocks). A fused chain's spec names its external
    inputs and its terminal output, the only arena rows its stages
    touch."""
    return stage_rows([(off, rows) for off, (rows, _) in zip(spec.in_off,
                                                              spec.in_rows)],
                      (spec.out_off, spec.out_rows[0]), total_rows,
                      _sub(spec.dtype))


def block_stage_bytes(spec: OpSpec, total_rows: int) -> int:
    """Arena bytes one staged launch copies between HBM and VMEM, both
    directions (:func:`block_stage_spans`)."""
    st = block_stage_spans(spec, total_rows)
    rows = sum(n for _, n in st.spans) + st.out[1]
    return rows * spec.rowlen * _isz(spec.dtype)


def _roll_rows(spec: OpSpec, total_rows: int) -> Tuple[int, int]:
    """Rows of a roll-grid launch's input window (one of its two buffers)
    and of its output slot."""
    c, k, _ = _addr_out(spec)
    slot = tile_writeback(spec.out_off, spec.out_shape[-3], c, k,
                          _sub(spec.dtype), total_rows)[1]
    return spec.win_rows - _tile_geom(spec)[1], slot


def launch_vmem_rows(spec: OpSpec, total_rows: int) -> int:
    """Arena rows one row-blocked launch allocates in VMEM: a roll-grid
    launch its double-buffered input window and its output slot, any other
    its staged buffer plus a fused chain's scratch."""
    if spec.win_starts:
        win_in, slot = _roll_rows(spec, total_rows)
        return 2 * win_in + slot
    return block_stage_spans(spec, total_rows).rows + spec.scratch_rows


def spec_filter_bytes(spec: OpSpec) -> int:
    """Bytes of the filters a launch holds in VMEM: the graph's filter
    shapes (:func:`repro.core.exec.ops.weights_for`) in the arena's tier."""
    if spec.kind == "fused":
        return sum(spec_filter_bytes(st) for st in spec.stages)
    if spec.kind in ("conv2d", "depthwise_conv2d"):
        kh, kw = spec.meta[:2]
        last = (spec.meta[8] if spec.kind == "depthwise_conv2d"
                else spec.out_shape[-1])
        n = kh * kw * spec.in_shape[0][-1] * last
    elif spec.kind == "fully_connected":
        n = spec.in_shape[0][-1] * spec.out_shape[-1]
    else:
        return 0
    return n * _isz(spec.dtype)


def _block_kernel(a_ref, *rest, spec: OpSpec, staging: Staging):
    """Staged launch: refs are (arena in HBM, *weights, the aliased arena
    in HBM, the packed VMEM buffer[, chain scratch], DMA semaphores). The
    DMA-aligned spans of the op's blocks are copied into the buffer
    (``staging``; every copy starts before the first wait), each operand is
    addressed at its arena row shifted by its span's static offset, and
    only the output block's span goes back to HBM."""
    nw = spec_weight_count(spec)
    w_refs, o_ref, buf = rest[:nw], rest[nw], rest[nw + 1]
    sems = rest[-1]
    cps = [pltpu.make_async_copy(o_ref.at[pl.ds(lo, n), :],
                                 buf.at[pl.ds(base, n), :], sems.at[i])
           for i, ((lo, n), base) in enumerate(zip(staging.spans,
                                                   staging.bases))]
    for cp in cps:
        cp.start()
    for cp in cps:
        cp.wait()
    if spec.kind == "fused":
        scratch = rest[nw + 2]
        _run_stages(spec, lambda st: _RoutedBlockMem(buf, scratch, st,
                                                     staging.row), w_refs)
    else:
        _BODIES[spec.kind](_BlockMem(buf, spec, staging.row), *w_refs,
                           spec=spec)
    out_lo, out_n = staging.out
    cp = pltpu.make_async_copy(buf.at[pl.ds(staging.row(out_lo), out_n), :],
                               o_ref.at[pl.ds(out_lo, out_n), :], sems.at[0])
    cp.start()
    cp.wait()


# ---------------------------------------------------------------------------
# The roll grid: arena in pl.ANY (HBM), a rolling input window in VMEM.
# Every HBM <-> VMEM copy moves whole DMA row groups (planner.DMA_ROWS).
# ---------------------------------------------------------------------------


def _table(vals: Tuple[int, ...], t):
    """``vals[t]`` for the grid index ``t``: a static select chain over a
    planner table of DMA-aligned rows (a captured jnp constant is not a
    legal kernel operand; tables are short)."""
    s = jnp.int32(vals[0])
    for i in range(1, len(vals)):
        s = jnp.where(t >= i, jnp.int32(vals[i]), s)
    return pl.multiple_of(s, DMA_ROWS)


def _stream_roll_kernel(a_ref, *rest, spec: OpSpec):
    """One output-row tile of a rolling-window conv/dw-conv/pool. Grid step
    ``t`` computes output rows ``[t*tr, min((t+1)*tr, oh))`` (``tr`` image
    rows = one sublane tile of packed arena rows) out of a
    double-buffered VMEM input window whose arena fetch start is the
    planner's static ``win_starts[t]`` (the single source of truth — the
    kernel just indexes the table). The tile-``t+1`` fetch is issued before
    the tile-``t`` wait; that prefetch may race rows the current tile is
    writing back, but those raced rows are never read except through
    clamped+masked taps (the O_s row invariant keeps every *live* read at
    arena rows >= the write frontier), so the overlap is benign. Fetches
    source the aliased *output* ref so the window observes all previous
    write-backs. The output slot mirrors the tile's DMA-aligned arena span
    (:func:`repro.core.planner.tile_writeback`): it is filled from the
    arena, the tile's rows land in it, and it goes back whole."""
    nw = spec_weight_count(spec)
    w_refs, o_ref = rest[:nw], rest[nw]
    in_win, out_buf, in_sems, out_sem = rest[nw + 1:]

    oh = spec.out_shape[-3]
    T = len(spec.win_starts)
    tr, tile_ar = _tile_geom(spec)
    c, k, _ = _addr_out(spec)
    wb, slot_rows = tile_writeback(spec.out_off, oh, c, k, _sub(spec.dtype),
                                   o_ref.shape[0])
    win_in = spec.win_rows - tile_ar
    t = pl.program_id(0)

    def fetch(tt):
        slot = jax.lax.rem(tt, 2)
        return pltpu.make_async_copy(
            o_ref.at[pl.dslice(_table(spec.win_starts, tt), win_in), :],
            in_win.at[slot],
            in_sems.at[slot])

    @pl.when(t == 0)
    def _():
        fetch(t).start()

    @pl.when(t + 1 < T)
    def _():
        fetch(t + 1).start()

    out_base = _table(wb, t)
    span = o_ref.at[pl.dslice(out_base, slot_rows), :]
    cp = pltpu.make_async_copy(span, out_buf, out_sem)
    cp.start()
    cp.wait()
    fetch(t).wait()

    row_lo = t * tr
    row_hi = jnp.minimum(row_lo + tr, oh)
    mem = _StreamRollMem(in_win.at[jax.lax.rem(t, 2)], out_buf, spec,
                         _table(spec.win_starts, t), out_base, row_lo, row_hi)
    _BODIES[spec.kind](mem, *w_refs, spec=spec)
    cp = pltpu.make_async_copy(out_buf, span, out_sem)
    cp.start()
    cp.wait()


def _kernel_weights(spec: OpSpec, weights: Tuple[jax.Array, ...]):
    """Weights as the kernel bodies index them: a depthwise filter
    ``(kh, kw, ic, mult)`` becomes ``(kh, kw, 1, ic*mult)`` so each tap's
    weights load as one lane row (reshaped outside the kernel)."""
    kinds = ([st.kind for st in spec.stages if st.kind in WEIGHTED_KINDS]
             if spec.kind == "fused" else [spec.kind])
    return tuple(
        w.reshape(w.shape[0], w.shape[1], 1, -1)
        if kind == "depthwise_conv2d" else w
        for kind, w in zip(kinds, weights))


def apply_op(arena: jax.Array, spec: OpSpec, weights: Tuple[jax.Array, ...],
             interpret: bool, vmem_limit: Optional[int] = None) -> jax.Array:
    """Run one op in-place on the shared arena (flat 1-D byte buffer, or a
    row-blocked 2-D typed buffer in HBM that the launch stages or rolls
    through VMEM, per the spec); returns the (aliased) arena.
    ``vmem_limit`` (bytes) is handed to Mosaic as the kernel's scoped-VMEM
    limit when compiling."""
    weights = _kernel_weights(spec, weights)
    fused = spec.kind == "fused"
    if not spec.rowlen:
        fn = pl.pallas_call(
            functools.partial(_flat_kernel, spec=spec),
            out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
            input_output_aliases={0: 0},
            scratch_shapes=([pltpu.VMEM((spec.scratch_rows,), jnp.uint8)]
                            if fused else []),
            interpret=interpret,
            name=kernel_name(spec),
        )
        return fn(arena, *weights)
    dt = _jnp_dtype(spec.dtype)
    L = spec.rowlen
    if spec.win_starts:                        # roll grid: conv/dw/pool
        kernel = functools.partial(_stream_roll_kernel, spec=spec)
        grid = (len(spec.win_starts),)
        win_in, slot = _roll_rows(spec, arena.shape[0])
        scratch = [
            pltpu.VMEM((2, win_in, L), dt), pltpu.VMEM((slot, L), dt),
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA(())]
    else:       # staged: one launch for a whole chain, its intermediates
        # in the chain scratch
        staging = block_stage_spans(spec, arena.shape[0])
        kernel = functools.partial(_block_kernel, spec=spec, staging=staging)
        grid = ()
        scratch = ([pltpu.VMEM((staging.rows, L), dt)]
                   + ([pltpu.VMEM((spec.scratch_rows, L), dt)]
                      if fused else [])
                   + [pltpu.SemaphoreType.DMA((len(staging.spans),))])
    params = ({} if interpret or vmem_limit is None else dict(
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)))
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * len(weights),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        input_output_aliases={0: 0},            # the arena is donated through
        scratch_shapes=scratch,
        interpret=interpret,
        name=kernel_name(spec),
        **params,
    )
    return fn(arena, *weights)


def lower_program(specs: Tuple[OpSpec, ...], interpret: bool,
                  vmem_limit: Optional[int] = None):
    """Jit-compiled executor for a spec sequence: ``fn(arena, *weights) ->
    arena``. The arena argument is donated, so together with the per-op
    aliasing the whole network runs in one shared buffer. Cached on the spec
    content — structurally identical plans share the compiled program."""
    return _lower_program_cached(tuple(specs), bool(interpret), vmem_limit)


@functools.lru_cache(maxsize=128)
def _lower_program_cached(specs: Tuple[OpSpec, ...], interpret: bool,
                          vmem_limit: Optional[int]):
    weight_counts = tuple(spec_weight_count(s) for s in specs)

    def run(arena, *wflat):
        i = 0
        for spec, nw in zip(specs, weight_counts):
            arena = apply_op(arena, spec, wflat[i:i + nw], interpret,
                             vmem_limit)
            i += nw
        return arena

    return jax.jit(run, donate_argnums=0)
