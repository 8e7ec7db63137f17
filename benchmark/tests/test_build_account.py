"""build_account.py and the seven readers built on it (PR 56), against a trace
recorded on one v5e chip (``record_build_counters.py``: a set-up's builds, two
units of five ``TrainStep.step``s, then a rebuild inside the session) and
against hand-made events: the FIRST ``tft.build.counters`` of the first traced
unit and not the sum of a unit's five, the slowest group, ``None`` for a program
without the event, an exact 0 kept."""

import json
import os
import types

import pytest

import build_account
import measure
import program_spans as ps
from common import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TRACE = os.path.join(HERE, "recorded_build_counters_v5e.xplane.pb")
PARENT = os.path.join(HERE, "recorded_program_spans_v5e.xplane.pb")  # a program without the account
# reader -> the stat of tft.build.counters it reads
READERS = {
    "build_trace_s_in_setup": "trace_s",
    "build_lower_s_in_setup": "lower_s",
    "build_load_s_in_setup": "load_s",
    "build_compile_s_in_setup": "compile_s",
    "build_cache_misses_in_setup": "cache_misses",
    "build_step_program_s_in_setup": "step_program_s",
    "step_program_first_call_s": "first_call_s",
}
STATS = set(READERS.values()) | {"builds", "cache_hits", "retrieval_s", "listener_errors"}


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(*paths):
    """A run as ``run.py`` hands it to a reader, one group a path."""
    results = [
        {"group": g, "device": {"platform": "tpu"}, **({"trace": {"xplane": p}} if p else {})}
        for g, p in enumerate(paths)
    ]
    return measure.Run({}, {}, {"steps_per_unit": 5}, None, results)


@pytest.fixture(scope="module")
def raw():
    """Straight from the file: the units and every ``tft.build.*`` event
    (start, name, line, stats) in order of start."""
    from jax.profiler import ProfileData

    units, events = [], []
    for plane in ProfileData.from_file(TRACE).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name == "bench_unit":
                    units.append((ev.start_ns, ev.start_ns + ev.duration_ns, i))
                elif ev.name.startswith("tft.build."):
                    events.append((ev.start_ns, ev.name, i, dict(ev.stats)))
    return sorted(units), sorted(events, key=lambda e: e[0])


def test_the_recorded_trace_is_what_the_program_leaves(raw):
    units, events = raw
    assert len(units) == 2
    counters = [e for e in events if e[1] == "tft.build.counters"]
    per_unit = [[e for e in counters if lo <= e[0] < hi] for lo, hi, _ in units]
    assert [len(u) for u in per_unit] == [5, 5]  # one a TrainStep.step
    first = per_unit[0][0][3]
    assert set(first) == STATS
    # the set-up's builds ended before the window: every step repeats the totals
    assert all(e[3] == first for u in per_unit for e in u)
    assert first["builds"] > 0 and first["trace_s"] > 0 and first["lower_s"] > 0
    assert first["step_program_s"] > 0 and first["first_call_s"] > 0 and first["listener_errors"] == 0
    assert first["builds"] >= first["cache_hits"] + first["cache_misses"]
    # the builder's seconds are the sum of the two sides of the cache
    assert (first["load_s"] > 0) == (first["cache_hits"] > 0)
    # after the units, inside the session: a rebuild, by stage and by name on
    # the thread that built (here the main thread, the units' own line)
    rebuilt = [e for e in events if e[0] > units[-1][1] and e[1] != "tft.build.counters"]
    assert {e[2] for e in rebuilt} == {units[0][2]}
    fused = [(e[1], e[3]) for e in rebuilt if e[3]["program"] == "tft_fused"]
    assert [n for n, _ in fused] == ["tft.build.trace", "tft.build.lower", "tft.build.compile"]
    assert fused[2][1]["cache"] in ("hit", "miss") and fused[2][1]["retrieval_s"] >= 0
    # ... and the next call's totals say what it cost
    last = counters[-1][3]
    assert last["builds"] > first["builds"] and last["first_call_s"] > first["first_call_s"]
    assert last["step_program_s"] > first["step_program_s"]


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_the_first_event_of_the_first_unit(name, raw):
    units, events = raw
    lo, hi, _ = units[0]
    first = next(e[3] for e in events if e[1] == "tft.build.counters" and lo <= e[0] < hi)
    value = reader(name).compute(a_run(TRACE))
    assert value == first[READERS[name]]
    assert isinstance(value, float)
    # two groups with the same trace: the most, not a sum or a mean of sums
    assert reader(name).compute(a_run(TRACE, TRACE)) == value


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_event_reads_none(name):
    mod = reader(name)
    assert mod.compute(a_run(PARENT)) is None  # an older commit's trace
    assert mod.compute(a_run(None)) is None  # an untraced run
    assert mod.compute(a_run(os.path.join(HERE, "no_such_file.xplane.pb"))) is None
    # one group of two without it: the one that has it is read
    assert mod.compute(a_run(PARENT, TRACE)) == mod.compute(a_run(TRACE))


def a_trace(units, counters):
    """A hand-made trace: ``counters`` are (start_ns, stats)."""
    events = sorted(
        [(s, s, build_account.COUNTERS, 1, stats) for s, stats in counters]
        + [(50.0, 60.0, ps.PREFIX + "build.compile", 1, {"program": "tft_fused", "cache": "hit"})]
    )
    return types.SimpleNamespace(units=units, events=events)


def test_hand_made_events_first_of_the_first_unit_slowest_group_exact_zero(monkeypatch):
    units = [(100.0, 200.0), (200.0, 300.0)]

    def stats(x):
        return {"trace_s": x, "compile_s": 0.0, "cache_misses": 0, "load_s": x / 2}

    traces = {
        # an event before the first unit (warm-up, still in the session) and a
        # unit's five that grow: the first INSIDE the unit is the reading
        "g0": a_trace(units, [(90.0, stats(1.0))] + [(100.0 + 10 * i, stats(2.0 + i)) for i in range(5)] + [(210.0, stats(9.0))]),
        "g1": a_trace(units, [(150.0, stats(5.0)), (160.0, stats(6.0))]),
        "g2": a_trace(units, [(250.0, stats(7.0))]),  # nothing in the first unit
        "old": a_trace(units, []),
    }
    monkeypatch.setattr(ps, "load", lambda r: traces.get(r.get("trace")))
    run = types.SimpleNamespace(results=[{"trace": "g0"}, {"trace": "g1"}, {"trace": "g2"}, {"trace": "old"}, {}])
    assert build_account.first_counters(traces["g0"]) == stats(2.0)
    assert build_account.first_counters(traces["g2"]) is None
    assert build_account.slowest_group(run, "trace_s") == 5.0
    assert build_account.slowest_group(run, "load_s") == 2.5
    # an exact 0 is a reading, as a float; a stat the program does not carry is not
    for key in ("compile_s", "cache_misses"):
        zero = build_account.slowest_group(run, key)
        assert zero == 0 and zero is not None and isinstance(zero, float)
    assert build_account.slowest_group(run, "first_call_s") is None
    run.results = [{"trace": "g2"}, {"trace": "old"}, {}]
    assert build_account.slowest_group(run, "trace_s") is None
    assert reader("build_compile_s_in_setup").compute(types.SimpleNamespace(results=[{"trace": "g1"}])) == 0.0


def test_the_seven_are_listed_for_every_cell_as_counters():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READERS:
        entry = listed[name]
        assert "workloads" not in entry  # every cell, as compile_s_in_setup
        assert (entry["layer"], entry["moves"], entry["better"]) == ("compile cache", "setup_s", "lower")
        assert entry["source"] == "program_counter"  # the contract's rehearsal admits an exact 0
        assert entry["unit"] == ("builds" if name == "build_cache_misses_in_setup" else "s")
        assert reader(name).NAME == name
