"""program_spans.py and the readers built on it, against a trace recorded on
one TPU v5e chip (``record_program_spans.py``): three traced units of the
``ft`` loop at a small size, one group, eight buckets a step. What the helper
returns is compared with the same quantity worked out here from the raw
events, and with numbers read off the file when it was recorded."""

import glob
import os

import pytest

import measure
from common import load_module
import program_spans as ps
import reduce_trace as rt
import scope_metrics

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TRACE = os.path.join(HERE, "recorded_program_spans_v5e.xplane.pb")
OLDER = os.path.join(HERE, "recorded_v5e.xplane.pb")  # no program spans in it
EXCHANGE_BYTES = 4 * 115008  # the recording's parameters, float32 gradients
NEW = (
    "exchange_d2h_wait_s", "exchange_pack_s", "exchange_ring_s", "exchange_h2d_s",
    "exchange_unattributed_s", "exchange_user_cpu_s", "exchange_sys_cpu_s",
    "commit_prepare_s", "loss_sync_s", "step_unattributed_s", "grads_device_s", "apply_device_s",
)


@pytest.fixture(scope="module")
def trace():
    return ps.Trace(TRACE)


@pytest.fixture(scope="module")
def raw():
    """(host events by line, device op intervals, module events) straight
    from the file."""
    from jax.profiler import ProfileData

    lines, ops, modules = [], [], []
    for plane in ProfileData.from_file(TRACE).planes:
        for line in plane.lines:
            events = [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name) for e in line.events]
            if plane.name.startswith("/host:CPU"):
                lines.append(events)
            elif plane.name.startswith("/device:TPU") and line.name == "XLA Ops":
                ops += [(s, e) for s, e, _ in events]
            elif plane.name.startswith("/device:TPU") and line.name == "XLA Modules":
                modules += events
    return lines, ops, modules


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(path, platform="tpu"):
    """A run as ``run.py`` hands it to a reader: the worker's reduced trace
    (``reduce_trace.reduce_file``) and the path of the raw one."""
    result = {"group": 0, "device": {"platform": platform}}
    if path is not None:
        result["trace"] = {"xplane": path}
        try:
            result["trace"].update(rt.reduce_file(path, []))
        except Exception:  # a file that is gone or torn: the path alone
            pass
    return measure.Run({}, {}, {"steps_per_unit": 1}, None, [result])


def test_units_threads_and_buckets(trace, raw):
    lines, _, _ = raw
    assert len(trace.units) == 3
    (main,) = [ln for ln in lines if any(n == "bench_unit" for _, _, n in ln)]
    assert sorted((s, e) for s, e, n in main if n == "bench_unit") == trace.units
    # every piece of the step once a unit on the main thread, eight buckets
    for name in ("step", "quorum.start", "shard_batch", "grads", "exchange", "commit", "commit.prepare", "apply", "loss_sync",
                 "exchange.d2h_issue", "exchange.plan", "exchange.tail_wait", "exchange.reassemble", "exchange.counters"):
        assert [len(trace._in_unit(u, ps.PREFIX + name, True)) for u in trace.units] == [1, 1, 1], name
    for name in ("exchange.d2h_wait", "exchange.pack", "exchange.submit"):
        assert [len(trace._in_unit(u, ps.PREFIX + name, True)) for u in trace.units] == [8, 8, 8], name
    for name in ("exchange.ring", "exchange.h2d"):
        assert [len(trace._in_unit(u, ps.PREFIX + name, False)) for u in trace.units] == [8, 8, 8], name
    # the ring never runs on the main thread
    assert trace.seconds(ps.PREFIX + "exchange.ring", main_only=True) == [0.0, 0.0, 0.0]
    assert trace.stat(ps.PREFIX + "exchange.d2h_wait", "bytes") == [EXCHANGE_BYTES] * 3
    assert trace.stat(ps.PREFIX + "exchange.ring", "bytes") == [EXCHANGE_BYTES] * 3
    assert trace.stat(ps.PREFIX + "exchange.counters", "buckets") == [8.0] * 3
    assert trace.stat(ps.PREFIX + "exchange.counters", "no_such_stat") is None
    assert trace.seconds(ps.PREFIX + "no.such.span") is None and trace.self_seconds(ps.PREFIX + "no.such.span") is None


def test_seconds_are_the_sums_of_the_raw_events(trace, raw):
    lines, _, _ = raw
    # read off the file when it was recorded
    assert trace.units == [(49352758.0, 64614647.0), (65144207.0, 79846526.0), (80261116.0, 95195535.0)]
    assert trace.seconds("tft.exchange") == pytest.approx([0.010264259, 0.009979029, 0.010116909], rel=1e-9)
    assert trace.seconds("tft.exchange.pack") == pytest.approx([0.002296196, 0.001385098, 0.00209706], rel=1e-9)
    assert trace.seconds("tft.exchange.h2d") == pytest.approx([0.005820299, 0.00590578, 0.00619162], rel=1e-9)
    assert trace.seconds("tft.exchange.h2d", main_only=True) == [0.0, 0.0, 0.0]  # all on the op thread here
    assert trace.self_seconds("tft.exchange") == pytest.approx([0.000815694, 0.00078934, 0.00084656], rel=1e-6)
    assert trace.self_seconds("tft.step") == pytest.approx([0.00065274, 0.00057651, 0.00061332], rel=1e-6)
    # the quorum thread's span is not the main thread's call
    assert trace.seconds("tft.quorum", main_only=True) == [0.0, 0.0, 0.0] and all(trace.seconds("tft.quorum"))
    for name in ("tft.exchange.pack", "tft.exchange.h2d", "tft.exchange.ring", "tft.loss_sync"):
        expect = [
            sum(e - s for ln in lines for s, e, n in ln if n == name and lo <= s < hi) / 1e9
            for lo, hi in trace.units
        ]
        assert trace.seconds(name) == pytest.approx(expect, rel=1e-12)
        assert all(v > 0 for v in expect)


def test_the_split_of_the_exchange_closes(trace):
    """d2h_issue + plan + sum(d2h_wait + pack + submit) + tail_wait + reassemble
    (+ what of h2d ran inline, + the zero-length counters) + unattributed =
    tft.exchange, on the main thread."""
    pieces = ("d2h_issue", "plan", "d2h_wait", "pack", "submit", "tail_wait", "reassemble", "h2d", "counters")
    covered = [
        sum(trace.seconds(ps.PREFIX + "exchange." + p, main_only=True)[i] for p in pieces) for i in range(3)
    ]
    whole = trace.seconds(ps.PREFIX + "exchange", main_only=True)
    rest = trace.self_seconds(ps.PREFIX + "exchange")
    for i in range(3):
        assert covered[i] + rest[i] == pytest.approx(whole[i], rel=1e-9)
        assert 0 < rest[i] < 0.2 * whole[i]
    # a step's self time is what lies between its pieces
    assert all(0 < s < 0.2 * w for s, w in zip(trace.self_seconds(ps.PREFIX + "step"), trace.seconds(ps.PREFIX + "step")))


def test_idle_seconds_are_span_time_minus_device_busy_time(trace, raw):
    _, ops, _ = raw
    name = ps.PREFIX + "exchange.d2h_wait"
    idle = trace.idle_seconds([name])[name]
    for i, (lo, hi) in enumerate(trace.units):
        spans = [(s, e) for s, e, *_ in trace._in_unit((lo, hi), name, True)]
        expect = sum((e - s) / 1e9 - rt.union_seconds(ops, s, e) for s, e in spans)
        assert idle[i] == pytest.approx(expect, rel=1e-9, abs=1e-12)
        assert 0 <= idle[i] <= trace.seconds(name)[i] * (1 + 1e-9)
    # the chip has nothing to do while this small exchange runs: all of it idle
    assert idle == pytest.approx([0.000248639, 0.00027281, 0.00025661], rel=1e-9)
    # innermost wins: the exchange's own row holds what its pieces do not
    both = trace.idle_seconds([ps.PREFIX + "exchange", name])
    assert both[name] == pytest.approx(idle)
    assert all(v >= 0 for v in both[ps.PREFIX + "exchange"])
    with pytest.raises(ValueError, match="at most 8"):
        trace.idle_seconds([f"tft.s{i}" for i in range(9)])


def test_program_seconds_come_from_the_modules_line(raw):
    """A unit's ``programs`` row: the ``XLA Modules`` events of the runs it launched, by name."""
    _, ops, modules = raw
    units = rt.reduce_file(TRACE, [])["units"]
    for program in ("jit_tft_grads", "jit_tft_apply"):
        expect = [(e - s) / 1e9 for s, e, n in sorted(modules) if n.split("(")[0] == program]
        assert [u["programs"][program] for u in units] == pytest.approx(expect, rel=1e-12)
    assert [u["programs"]["jit_tft_grads"] for u in units] == pytest.approx([7.1326e-05, 7.1311e-05, 7.1577e-05], rel=1e-9)
    assert [u["programs"]["jit_tft_apply"] for u in units] == pytest.approx([1.0841e-05, 1.0128e-05, 1.0431e-05], rel=1e-9)
    # every op ran inside a program run; at this miniature size the gaps between a run's ops are 4 % of it
    # (at the cells' size 0.002-0.012 %, PERF.md section 5: what scope_metrics.SUM_TOLERANCE is set against)
    for u in units:
        assert u["unlaunched_s"] == 0.0
        assert sum(u["by_scope"].values()) == pytest.approx(sum(u["programs"].values()), rel=5e-2)
    run = a_run(TRACE)
    assert scope_metrics.program_seconds(run, "tft_grads") == pytest.approx((7.1326e-05 + 7.1311e-05) / 2, rel=1e-9)
    assert scope_metrics.program_seconds(run, "tft_fused") is None


def test_a_program_that_starts_after_its_unit_ends_counts_for_that_step():
    """``apply`` is the last thing a step dispatches: at a real size it starts
    on the device as the unit ends or in the gap before the next one, and the
    last one is cut where the trace stops (seen in the first traced run of
    ``olmo1b-1g.ft-steady``: 25.4, 25.4 and 2.1 ms). The unit that launched
    a run owns it, whenever the device ran it."""
    import types

    def line(name, events):
        return types.SimpleNamespace(name=name, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d, stats=stats) for n, s, d, stats in events
        ])

    runs = [("jit_tft_grads(2)", 1.0, 50.0, 1), ("jit_tft_apply(1)", 99.0, 25.0, 2), ("jit_tft_apply(1)", 212.0, 25.0, 3),
            ("jit_tft_apply(1)", 319.0, 2.0, 4)]
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        line(rt.MODULE_LINE, [(n, s, d, (("run_id", i),)) for n, s, d, i in runs]),
        line(rt.OP_LINE, [("%op", s, d, ()) for _, s, d, _ in runs]),
    ])
    host = types.SimpleNamespace(name="/host:CPU", lines=[line("main", [
        (rt.UNIT_SPAN, 0.0, 100.0, ()), (rt.UNIT_SPAN, 110.0, 100.0, ()), (rt.UNIT_SPAN, 220.0, 100.0, ()),
        (rt.ENQUEUE_EVENT, 0.5, 0.1, (("run_id", 1),)), (rt.ENQUEUE_EVENT, 98.0, 0.1, (("run_id", 2),)),
        (rt.ENQUEUE_EVENT, 209.0, 0.1, (("run_id", 3),)), (rt.ENQUEUE_EVENT, 318.0, 0.1, (("run_id", 4),)),
    ])])
    units = rt.reduce_planes([device, host], [])["units"]
    assert [u["programs"].get("jit_tft_apply") for u in units] == pytest.approx([25e-9, 25e-9, 2e-9])
    assert [u["programs"].get("jit_tft_grads") for u in units] == [pytest.approx(50e-9), None, None]
    run = measure.Run({}, {}, {"steps_per_unit": 1}, None, [{"group": 0, "trace": {"units": units}}])
    assert scope_metrics.program_seconds(run, "tft_apply") == pytest.approx(25e-9)  # the cut one is left out


def test_every_new_reader_gives_the_median_of_the_traced_steps(trace):
    run = a_run(TRACE)
    median = measure.median
    expect = {
        "exchange_d2h_wait_s": median(trace.idle_seconds([ps.PREFIX + "exchange.d2h_wait"])[ps.PREFIX + "exchange.d2h_wait"]),
        "exchange_pack_s": median(trace.seconds(ps.PREFIX + "exchange.pack")),
        "exchange_ring_s": median(trace.seconds(ps.PREFIX + "exchange.ring")),
        "exchange_h2d_s": median(trace.seconds(ps.PREFIX + "exchange.h2d")),
        "exchange_unattributed_s": median(trace.self_seconds(ps.PREFIX + "exchange")),
        # the kernel's tick is 10 ms and this exchange lasts 10 ms: 0.02, 0.01, 0.02 s of user
        # time, and three times 0 s of system time: under the tick, and a reading all the same
        "exchange_user_cpu_s": 0.02,
        "exchange_sys_cpu_s": 0.0,
        "commit_prepare_s": median(trace.seconds(ps.PREFIX + "commit.prepare")),
        "loss_sync_s": median(trace.seconds(ps.PREFIX + "loss_sync")),
        "step_unattributed_s": median(trace.self_seconds(ps.PREFIX + "step")),
        "grads_device_s": (7.1326e-05 + 7.1311e-05) / 2,  # the last unit's is left out
        "apply_device_s": (1.0841e-05 + 1.0128e-05) / 2,
    }
    assert set(expect) == set(NEW)
    assert trace.stat(ps.PREFIX + "exchange.counters", "stime_s") == [0.0, 0.0, 0.0]
    for name in NEW:
        got = reader(name).compute(run)
        assert got == pytest.approx(expect[name], rel=1e-9), name
        assert got > 0 or name == "exchange_sys_cpu_s", name


@pytest.mark.parametrize("name", NEW)
def test_a_reader_returns_none_where_there_is_nothing_to_read(name, tmp_path):
    compute = reader(name).compute
    assert compute(a_run(OLDER)) is None  # a program without the spans
    assert compute(a_run(None)) is None  # an untraced run
    assert compute(a_run(str(tmp_path / "gone.xplane.pb"))) is None
    torn = tmp_path / "torn.xplane.pb"
    torn.write_bytes(open(TRACE, "rb").read()[:1000])
    assert compute(a_run(str(torn))) is None


def test_the_ring_reads_zero_where_the_exchange_ran_without_one(monkeypatch):
    class NoRing:
        units = [(0.0, 1.0), (1.0, 2.0), (2.0, 3.0)]

        def has(self, name):
            return name == ps.PREFIX + "exchange"

        def seconds(self, name, main_only=False):
            return [0.5, 0.6, 0.7] if self.has(name) else None

    monkeypatch.setattr(ps, "load", lambda result: NoRing())
    assert reader("exchange_ring_s").compute(a_run(TRACE)) == 0.0


def test_every_new_reader_has_its_file():
    have = {os.path.basename(p)[:-3] for p in glob.glob(os.path.join(BENCH, "layer_metrics", "*.py"))}
    assert set(NEW) <= have
