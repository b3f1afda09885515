"""CPU tests of the comparison that decides the benchmark's ``correct``.

- The control, the reference one precision step down, computed at each
  cell's own sizes, must read over the cell's limit.
- A whole run of each cell, with the chip check skipped and the program's
  NumPy arena executor standing in for the Pallas one (same plan, same
  arena, same weights), must read correct; with a fault planted under it
  (an answer altered where it is produced, half of a batch left out, one
  row of an early arena tensor clobbered) or with the control in the
  program's place, it must not.
"""
import numpy as np
import pytest

from bench import control, program, registry, run

CELLS = {
    "b1": "mobilenet_v1_0.25_128_int8.single_stream_b1",
    "b8": "mobilenet_v1_0.25_128_int8.offline_b8",
    "f32": "mobilenet_v1_1.0_224_f32.single_stream_b1",
    "stream": "mobilenet_v1_0.25_128_int8.single_stream_b1.streaming",
}
SEED = 2 ** 31 + 977


class _Host:
    platform, device_kind = "cpu", "cpu"

    def memory_stats(self):
        return {}


def _first_arena_op(cp):
    """The first op of the plan whose output lives in the arena (not in a
    fused chain's scratch) and is not the graph's output."""
    from repro.core.exec import unwrap_plan
    plan, _ = unwrap_plan(cp)
    return next(op for op in plan.order
                if op.output.storage().kind not in ("scratch", "output"))


class Clobbered:
    """The NumPy arena executor with one row of the first intermediate
    arena tensor of image 0 overwritten, after it is written and before it
    is read, by the row below it: what an unsafe overlap does to a live
    value. The warm-up calls pass through."""

    def __init__(self, row=0):
        self.row = row
        self.calls = 0

    def execute(self, cp, inputs, weights, quant=None):
        from repro.core.exec import unwrap_plan
        from repro.core.exec.numpy_backend import ArenaExec
        plan, graph = unwrap_plan(cp)
        self.calls += 1
        target = _first_arena_op(cp) if self.calls > run.WARMUP_CALLS \
            else None
        row = self.row

        class Exec(ArenaExec):
            def store_rows(self, op, rows, b=0):
                super().store_rows(op, rows, b)
                if op is target and b == 0:
                    out = op.output
                    view = self._view(out)
                    n = out.image_elems // out.shape[-3]
                    view[row * n:(row + 1) * n] = \
                        view[(row + 1) * n:(row + 2) * n]

        ex = Exec(graph, plan, inputs, 0, weights, quant)
        ex.run(plan.order)
        return {t.name: ex.load(t) for t in graph.tensors
                if t.kind == "output"}


class Control:
    """The control in the program's place: the reference one precision
    step down, on the run's own seed and inputs."""

    def __init__(self, cell, seed):
        self.cell, self.ref = cell, cell.reference()
        cfg = cell.config
        self.w, self.q = self.ref.make_params(
            cfg, seed, self.ref.calibrate(cfg), control=True)

    def execute(self, cp, inputs, weights, quant=None):
        (x,), (out,) = inputs.values(), program.io_names(cp)[1:]
        predict = lambda img: self.ref.predict(  # noqa: E731
            self.cell.config, self.w, self.q, img, control=True)
        batched = int(self.cell.traffic["batch"]) > 1
        return {out: np.stack([predict(i) for i in x]) if batched
                else predict(x)}


class Faulty:
    """The NumPy arena executor with ``fault`` applied to each output of
    the timed window (the warm-up calls pass through)."""

    def __init__(self, fault=None):
        from repro.core import exec as X
        self.inner = X.get_backend("numpy")
        self.fault = fault
        self.calls = 0

    def execute(self, cp, inputs, weights, quant=None):
        out = self.inner.execute(cp, inputs, weights, quant=quant)
        self.calls += 1
        if self.fault is not None and self.calls > run.WARMUP_CALLS:
            out = {k: self.fault(np.array(v)) for k, v in out.items()}
        return out


def altered(limits):
    """One class score of each answer changed where it is produced, by
    just over the cell's limit."""
    def fault(v):
        flat = v.reshape(-1)
        if v.dtype == np.int8:
            step = int(limits["max_lsb"]) + 1
            flat[0] = flat[0] - step if flat[0] > 0 else flat[0] + step
        else:
            flat[0] *= np.float32(np.exp(2 * limits["max_log_gap"]))
        return v
    return fault


def half_batch(v):
    """Half of the batch left out: the first half's answers stand in for
    the rest."""
    h = v.shape[0] // 2
    v[h:2 * h] = v[:h]
    return v


def drive(key, fault=None, executor=None):
    program.import_program()
    cell = registry.load_cell(CELLS[key])
    return run.run_cell(cell, SEED, 0.05, False, [_Host()], 0.0,
                        executor=executor or Faulty(fault))


def over(numbers, limits):
    return [n for n in limits if numbers[n] > limits[n]]


@pytest.mark.parametrize("key", ["b1", "b8", "f32"])
def test_control_reads_over_the_limit(key):
    cell = registry.load_cell(CELLS[key])
    limits = cell.config["check"]
    for seed in (11, 2 ** 31 + 5, 3 * 10 ** 9):
        assert over(control.reading(cell, seed), limits)


@pytest.mark.parametrize("key", ["b1", "b8", "f32"])
def test_control_in_the_programs_place_is_not_correct(key):
    cell = registry.load_cell(CELLS[key])
    r = drive(key, executor=Control(cell, SEED))
    assert not r["correct"]
    assert over({n: c["value"] for n, c in r["compared"].items()},
                cell.config["check"])


@pytest.mark.parametrize("key", ["b1", "b8", "f32"])
def test_clobbered_row_of_an_early_arena_tensor_is_not_correct(key):
    r = drive(key, executor=Clobbered())
    assert not r["correct"], r["compared"]
    assert r["failed"] >= 1


@pytest.mark.parametrize("key", ["b1", "b8", "f32"])
def test_sound_run_is_correct(key):
    r = drive(key)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("key", ["b1", "b8", "f32"])
def test_answer_altered_where_produced_is_not_correct(key):
    limits = registry.load_cell(CELLS[key]).config["check"]
    r = drive(key, altered(limits))
    assert not r["correct"]
    assert r["failed"] == r["attempted"]


def test_half_of_the_batch_left_out_is_not_correct():
    r = drive("b8", half_batch)
    assert not r["correct"]


def test_streaming_route_names_the_streaming_executor():
    cell = registry.load_cell(CELLS["stream"])
    assert program.ROUTES[cell.traffic["route"]] == {"mode": "streaming"}
