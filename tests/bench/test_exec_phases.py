"""CPU tests of the readers of the executor's phase spans: idle device
time filed under the program's ``dmo.*`` spans, per traced call."""
import pytest

from bench import registry, tracefile
from bench.tracefile import Event, Summary, Trace

PREP = ("dmo.resolve", "dmo.legalise", "dmo.seed_arena", "dmo.launch",
        "dmo.gather")
READERS = ("exec_prep_idle_ms_per_call", "exec_upload_idle_ms_per_call",
           "exec_fetch_idle_ms_per_call")
KERNEL = ('%run.1 = s8[96,768]{1,0} custom-call(s8[96,768]{1,0} %a), '
          'custom_call_target="tpu_custom_call"')


def _readers():
    return registry.metric_readers([{"name": n} for n in READERS])


def _summary(idle, calls=2):
    return Summary(calls, 0.1, 0.01, 0.01, 29.0, [], [list(kv) for kv in
                                                     idle.items()])


def test_a_gap_is_filed_under_the_phase_span_not_a_nested_jax_span():
    python = [
        Event("bench_call", 0, 100),
        Event("dmo.upload", 10, 30),         # [10, 40)
        Event("shard_args", 12, 26),         # nested in the upload
        Event("dmo.launch", 40, 10),         # [40, 50)
        Event("PjitFunction(run)", 41, 8),   # nested in the launch
    ]
    got = tracefile.attribute_gaps([(15, 45)], python)
    assert dict(got) == pytest.approx({"dmo.upload": 25, "dmo.launch": 5})


def test_phase_spans_leave_little_idle_time_unnamed():
    """A call made of the seven contiguous phases: every idle nanosecond
    inside it is filed under a phase, and the breakdown names them."""
    starts = [0, 10, 20, 25, 55, 60, 95]
    ends = starts[1:] + [100]
    names = PREP[:3] + ("dmo.upload", "dmo.launch", "dmo.fetch",
                        "dmo.gather")
    python = [Event("bench_call", 0, 100)] + [
        Event(n, s, e - s) for n, s, e in zip(names, starts, ends)]
    device = [Event(KERNEL, 62, 20)]        # runs inside the fetch
    s = tracefile.summarise(Trace({"/device:TPU:0": device}, python))
    idle = dict(s.idle_gaps)
    assert tracefile.UNSPANNED not in idle
    assert idle["dmo.fetch"] == pytest.approx(15e-9)
    assert idle["dmo.upload"] == pytest.approx(30e-9)
    r = _readers()
    ctx = {"trace": s}
    assert r["exec_upload_idle_ms_per_call"].read(ctx) == \
        pytest.approx(30e-6)
    assert r["exec_fetch_idle_ms_per_call"].read(ctx) == \
        pytest.approx(15e-6)
    assert r["exec_prep_idle_ms_per_call"].read(ctx) == \
        pytest.approx((25 + 5 + 5) * 1e-6)


def test_phase_readers_on_a_summary():
    idle = {"dmo.resolve": 0.001, "dmo.legalise": 0.002,
            "dmo.seed_arena": 0.0005, "dmo.upload": 0.009,
            "dmo.launch": 0.0005, "dmo.fetch": 0.003, "dmo.gather": 0.001,
            tracefile.UNSPANNED: 0.0004}
    ctx = {"trace": _summary(idle)}
    r = _readers()
    assert r["exec_prep_idle_ms_per_call"].read(ctx) == pytest.approx(2.5)
    assert r["exec_upload_idle_ms_per_call"].read(ctx) == \
        pytest.approx(4.5)
    assert r["exec_fetch_idle_ms_per_call"].read(ctx) == pytest.approx(1.5)


@pytest.mark.parametrize("name", READERS)
def test_phase_readers_read_nothing_from_a_program_without_the_spans(name):
    """A trace of a program that writes no dmo.* span (the idle time
    filed under JAX's own spans) gives no reading, and raises nothing."""
    idle = {"shard_args": 0.09, tracefile.UNSPANNED: 0.066,
            "np.asarray(jax.Array)": 0.024, "PjitFunction(run)": 0.0025}
    r = _readers()[name]
    assert r.read({"trace": _summary(idle)}) is None
    assert r.read({"trace": None}) is None
