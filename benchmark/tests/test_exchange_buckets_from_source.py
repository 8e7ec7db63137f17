"""exchange_buckets_from_source.py (PR 39), on synthetic traces of four groups
(``test_exchange_account``'s ``FakeTrace``) and on the recordings the benchmark
has, all of programs older than the counter: those read ``None``, never an
error, and the line leaves the metric out."""

import json
import os

import pytest

from test_exchange_account import BENCH, CELL, OLDER, PARENT, TRACE, FakeTrace, a_run, four_groups, reader

NAME = "exchange_buckets_from_source"


def test_the_median_over_steps_then_the_mean_over_groups(monkeypatch):
    every = FakeTrace(buckets=10, buckets_from_source=10)
    run = four_groups(monkeypatch, [every] * 4)
    assert reader(NAME).compute(run) == 10
    assert reader(NAME).compute(run) == reader("exchange_buckets_avg_in_ring").compute(
        four_groups(monkeypatch, [FakeTrace(buckets_avg_in_ring=10)] * 4)
    )
    # a group that packed (error feedback, the Python ring) pulls the mean
    # down; a step that did pulls its group's median only from the middle
    packed = FakeTrace(buckets=10, buckets_from_source=0)
    once = FakeTrace(buckets=10, buckets_from_source=[10, 0, 10])
    assert reader(NAME).compute(four_groups(monkeypatch, [every, every, packed, once])) == 7.5
    # an exact 0 is a reading: every bucket packed, as on a plane without sources
    assert reader(NAME).compute(four_groups(monkeypatch, [packed] * 4)) == 0


@pytest.mark.parametrize("path", [TRACE, PARENT, OLDER, None], ids=["pr38", "pr37", "no-spans", "untraced"])
def test_a_program_without_the_counter_reads_none(path, monkeypatch):
    assert reader(NAME).compute(a_run(path)) is None
    assert reader(NAME).compute(four_groups(monkeypatch, [FakeTrace(buckets=10)] * 4)) is None


def test_the_entry_is_appended_and_names_the_cell_and_the_readers_file():
    bench = json.load(open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    mod = reader(NAME)
    assert entry == {
        "name": mod.NAME, "unit": mod.UNIT, "better": "higher", "source": mod.SOURCE,
        "layer": mod.LAYER, "moves": mod.MOVES, "workloads": [CELL],
    }
    assert mod.LAYER in {m["layer"] for m in bench["per_layer"] if m["name"] != NAME}
    assert CELL in [w["name"] for w in bench["workloads"]]
