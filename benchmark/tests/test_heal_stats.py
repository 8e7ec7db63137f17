"""The three readers of a healer's ``heal_stats`` (PR 49) on recorded
``heal_end`` lines of the four-group cell (``recorded_heal_end.json``: the
parent's, whose program has no ``streams``, and the change's)."""

import json
import os
import types

import pytest

from test_exchange_account import BENCH, CELL, reader

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = json.load(open(os.path.join(HERE, "recorded_heal_end.json")))
NAMES = ("heal_meta_s", "heal_recv_s", "heal_fetch_streams")


def a_run(side):
    return types.SimpleNamespace(results=RECORDED[side]["results"])


@pytest.mark.parametrize(
    "side,name,want",
    [
        ("parent", "heal_meta_s", 16.9306),  # the slowest of three healers
        ("parent", "heal_recv_s", 19.1264),
        ("parent", "heal_fetch_streams", None),  # older than the counter
        ("change", "heal_meta_s", RECORDED["change"]["want"]["heal_meta_s"]),
        ("change", "heal_recv_s", RECORDED["change"]["want"]["heal_recv_s"]),
        ("change", "heal_fetch_streams", 7),
    ],
)
def test_a_recorded_line(side, name, want):
    assert reader(name).compute(a_run(side)) == want


@pytest.mark.parametrize("name", NAMES)
def test_a_run_without_a_heal_reads_none(name):
    empty = types.SimpleNamespace(results=[{"group": 0}, {"group": 1, "heal_events": []}])
    assert reader(name).compute(empty) is None
    # a heal_end of a transport that keeps no stats (the single-source path)
    bare = types.SimpleNamespace(results=[{"group": 1, "heal_events": [{"event": "heal_end", "duration_s": 3.0}]}])
    assert reader(name).compute(bare) is None


def test_one_stream_is_a_reading_and_the_least_healer_counts():
    def run(*streams):
        return types.SimpleNamespace(results=[
            {"group": g, "heal_events": [{"heal_stats": {"streams": s, "stages": {"meta_s": 1.0 + g, "recv_s": 2.0}}}]}
            for g, s in enumerate(streams, start=1)
        ])

    assert reader("heal_fetch_streams").compute(run(1, 1, 1)) == 1
    assert reader("heal_fetch_streams").compute(run(7, 2, 7)) == 2
    assert reader("heal_meta_s").compute(run(7, 7, 7)) == 4.0


def test_the_stages_of_the_change_add_up():
    for r in RECORDED["change"]["results"]:
        for e in r["heal_events"]:
            stats = e["heal_stats"]
            st = stats["stages"]
            assert st["recv_s"] >= st["alloc_s"] + st["fetch_s"] - 1e-3
            assert e["duration_s"] >= st["meta_s"] + st["recv_s"] + st["decode_s"]
            assert stats["source_stage"]["digest_workers"] > 1
            assert e["bytes"] == 7304702144 and stats["failures"] == {}


@pytest.mark.parametrize("name", NAMES)
def test_the_entry_is_appended_and_names_the_cell_and_the_readers_file(name):
    bench = json.load(open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    mod = reader(name)
    assert entry == {
        "name": mod.NAME, "unit": mod.UNIT, "better": "higher" if name == "heal_fetch_streams" else "lower",
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES, "workloads": [CELL],
    }
    (accepted,) = [m for m in bench["per_layer"] if m["name"] == "bootstrap_heal_s"]
    assert (entry["layer"], entry["moves"], entry["workloads"]) == (accepted["layer"], accepted["moves"], accepted["workloads"])
    # appended: after every entry the benchmark had (PR 47's is its last)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(name) > names.index("attn_core_recomputed_device_s")
