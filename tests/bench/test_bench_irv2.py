"""CPU tests of the Inception-ResNet-v2 cell at a small size: 75 px and one
block of each kind (``repeats`` 1, 1, 1), at the published widths.

- A whole run of the harness, with the chip check skipped, reads correct
  with the program's NumPy arena executor and with its Pallas program in
  interpret mode standing in, on two seeds.
- The bfloat16 control, one clobbered arena row of a join's output and
  answers swapped between images read not correct.
- The row-streamed residual add and channel concat agree bit for bit with
  the whole-tensor bodies they replace, and a spec whose in-place overlap
  the row order would clobber runs the whole-tensor body.
"""
import dataclasses

import numpy as np
import pytest

from bench import control, program, registry, run

CELL = "inception_resnet_v2_299_f32.single_stream_b1"
SMALL = {"resolution": 75, "repeats": [1, 1, 1]}
SEEDS = (2 ** 31 + 977, 3 * 10 ** 9 + 11)


class _Host:
    platform, device_kind = "cpu", "cpu"

    def memory_stats(self):
        return {}


def small_cell():
    """The cell with its configuration cut to 75 px and one block of each
    kind, and a pool of two frames."""
    cell = registry.load_cell(CELL)
    return dataclasses.replace(
        cell, config={**cell.config, **SMALL},
        traffic={**cell.traffic, "pool_calls": 2})


def drive(executor, seed=SEEDS[0]):
    program.import_program()
    return run.run_cell(small_cell(), seed, 0.05, False, [_Host()], 0.0,
                        executor=executor)


def backend(name):
    from repro.core import exec as X
    program.import_program()
    return X.get_backend(name)


class Swapped:
    """Each answer is the program's answer for the other frame of the pool,
    once both have been seen (the warm-up sees both)."""

    def __init__(self):
        self.inner = backend("numpy")
        self.seen = {}

    def execute(self, cp, inputs, weights, quant=None):
        key = next(iter(inputs.values())).tobytes()
        self.seen[key] = self.inner.execute(cp, inputs, weights, quant=quant)
        others = [v for k, v in self.seen.items() if k != key]
        return others[-1] if others else self.seen[key]


class ClobberedJoin:
    """The NumPy arena executor with row 0 of the first join's output
    (concat or add) overwritten by row 1 after it is written: what an
    unsafe overlap does to a live value. Warm-up calls pass through."""

    def __init__(self):
        self.calls = 0

    def execute(self, cp, inputs, weights, quant=None):
        from repro.core.exec import unwrap_plan
        from repro.core.exec.numpy_backend import ArenaExec
        plan, graph = unwrap_plan(cp)
        self.calls += 1
        target = next(op.output for op in plan.order
                      if op.kind in ("concat", "elementwise")) \
            if self.calls > run.WARMUP_CALLS else None

        class Exec(ArenaExec):
            def store_image(self, t, v, b):
                super().store_image(t, v, b)
                if t is target:
                    view = self._view(t)
                    n = t.image_elems // t.shape[-3]
                    view[:n] = view[n:2 * n]

        ex = Exec(graph, plan, inputs, 0, weights, quant)
        ex.run(plan.order)
        return {t.name: ex.load(t) for t in graph.tensors
                if t.kind == "output"}


class Control:
    """The reference one precision step down in the program's place."""

    def __init__(self, cell, seed):
        self.cell, self.ref = cell, cell.reference()
        self.w, _ = self.ref.make_params(cell.config, seed, None,
                                         control=True)

    def execute(self, cp, inputs, weights, quant=None):
        (x,), out = inputs.values(), program.io_names(cp)[1]
        return {out: self.ref.predict(self.cell.config, self.w, None, x,
                                      control=True)}


@pytest.mark.parametrize("name", ["numpy", "pallas"])
@pytest.mark.parametrize("seed", SEEDS)
def test_program_agrees_with_the_reference(name, seed):
    r = drive(backend(name), seed)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert r["compared"]["max_log_gap"]["value"] < 1e-4


def test_reference_states_the_programs_graph():
    """Every op of the program's graph is a layer of the reference under
    the same name and output shape, and nothing more."""
    from repro.core import zoo
    cell = small_cell()
    layers = {ly.name: ly for ly in cell.reference().layers(cell.config)}
    g = zoo.inception_resnet_v2(75, 4, (1, 1, 1))
    assert {op.name: tuple(op.output.shape) for op in g.ops} == \
        {n: ly.out_shape for n, ly in layers.items()}


def test_logits_stay_of_order_one_and_the_softmax_spread():
    cell = small_cell()
    ref = cell.reference()
    w, _ = ref.make_params(cell.config, SEEDS[0], None)
    img = np.random.default_rng(0).uniform(-1, 1, (75, 75, 3))
    vals = ref.forward_float(cell.config, w, img)
    assert 0.1 < vals["logits"].std() < 10
    assert vals["prob"].max() < 0.5 and vals["prob"].min() > 1e-9


def test_control_reads_over_the_limit():
    cell = small_cell()
    limits = cell.config["check"]
    numbers = control.reading(cell, SEEDS[1])
    assert numbers["max_log_gap"] > limits["max_log_gap"]


@pytest.mark.parametrize("fault", ["control", "clobbered_join", "swapped"])
def test_fault_reads_not_correct(fault):
    executor = {"control": lambda: Control(small_cell(), SEEDS[0]),
                "clobbered_join": ClobberedJoin,
                "swapped": Swapped}[fault]()
    r = drive(executor)
    assert not r["correct"], r["compared"]
    assert r["failed"] >= 1


def test_layer_work_of_the_joins():
    cell = small_cell()
    ref = cell.reference()
    by = {ly.name: ly for ly in ref.layers(cell.config)}
    # the 7x7x320 add: one op per output element; inputs and output in f32
    n = 7 * 7 * 320
    assert ref.layer_work(by["m35_0_add"], 4) == (n, 3 * n * 4)
    cat = by["m5b_cat"]
    assert ref.layer_work(cat, 4, 2) == (0, 2 * 2 * n * 4)
    pool = by["m5b_p"]
    m = 7 * 7 * 192
    assert ref.layer_work(pool, 4) == (9 * m, 2 * m * 4)
    assert ref.layer_work(by["m35_0_up"], 4) is None


# ---------------------------------------------------------------------------
# The row-streamed join bodies
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_plan():
    from repro.core import zoo
    from repro.core.exec.pallas_backend import PallasExecutor
    program.import_program()
    cp = program.compile_graph(zoo.inception_resnet_v2(75, 4, (1, 1, 1)), 1)
    bp = cp.legalised()
    bp.validate()
    return bp, PallasExecutor(mode="interpret").lower_blocks(bp)


def _image(arena, spec, i=None):
    """Input ``i`` (the output for ``None``) of a blocked spec read out of
    the arena, one image row at a time."""
    shape = spec.out_shape if i is None else spec.in_shape[i]
    off = spec.out_off if i is None else spec.in_off[i]
    c, k, rl = (spec.out_addr if i is None else spec.in_addr[i]) or (
        1, 1, shape[-2] * shape[-1])
    rows = []
    for y in range(shape[-3]):
        if c > 1:
            rows.append(arena[off + y // c, (y % c) * rl:(y % c + 1) * rl])
        else:
            rows.append(arena[off + y * k:off + (y + 1) * k].reshape(-1)[:rl])
    return np.stack(rows).reshape(shape)


def _run(spec, arena, monkeypatch, whole):
    import jax.numpy as jnp

    from repro.kernels import arena_ops
    with monkeypatch.context() as m:
        if whole:
            m.setattr(arena_ops, "_row_streamable", lambda mem, spec: False)
        return np.asarray(arena_ops.apply_op(jnp.asarray(arena), spec, (),
                                             interpret=True))


@pytest.mark.parametrize("name", ["m35_0_add", "m5b_cat", "m35_0_cat",
                                  "rb_cat"])
def test_row_streamed_join_matches_the_whole_tensor_body(small_plan, name,
                                                         monkeypatch):
    from repro.kernels import arena_ops
    bp, specs = small_plan
    spec = next(s for s in specs if s.name == name)
    assert arena_ops._row_streamable(arena_ops._BlockMem(None, spec), spec)
    arena = np.random.default_rng(3).standard_normal(
        (bp.total_rows, bp.arena_rowlen)).astype(np.float32)
    rows = _run(spec, arena, monkeypatch, whole=False)
    whole = _run(spec, arena, monkeypatch, whole=True)
    np.testing.assert_array_equal(_image(rows, spec), _image(whole, spec))
    ins = [_image(arena, spec, i) for i in range(len(spec.in_shape))]
    want = ins[0] + ins[1] if spec.kind == "elementwise" \
        else np.concatenate(ins, axis=-1)
    np.testing.assert_array_equal(_image(rows, spec), want)
    # rows outside the output's block are left as they were
    lo, n = spec.out_off, spec.out_rows[0]
    np.testing.assert_array_equal(np.delete(rows, range(lo, lo + n), 0),
                                  np.delete(arena, range(lo, lo + n), 0))


def test_join_whose_overlap_the_row_order_would_clobber_runs_whole(
        small_plan, monkeypatch):
    """The add's output one arena row above its first input: writing
    output row ``y`` would clobber input row ``y + 1`` before it is read,
    so the kernel takes the whole-tensor body and the sum stays right."""
    from repro.kernels import arena_ops
    bp, specs = small_plan
    add = next(s for s in specs if s.name == "m35_0_add")
    other = add.in_off[1]
    spec = dataclasses.replace(add, in_off=(other + 1, other),
                               out_off=other + 2)
    assert not arena_ops._row_streamable(arena_ops._BlockMem(None, spec),
                                         spec)
    arena = np.random.default_rng(4).standard_normal(
        (bp.total_rows, bp.arena_rowlen)).astype(np.float32)
    ins = [_image(arena, spec, i) for i in range(2)]
    got = _image(_run(spec, arena, monkeypatch, whole=False), spec)
    np.testing.assert_array_equal(got, ins[0] + ins[1])
