"""``kda_core_kernel_pct`` (PR 36): the share of the KDA core's device seconds
that ran in its Pallas kernels — on rows written by hand, where there is
nothing to read, on the paths the kernels' ops carry, and in ``BENCHMARK.json``."""

import os

import pytest

import hybrid_scopes
import measure
import subscopes
from common import load_json, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = load_json(os.path.join(BENCH, "configs", "kimi-linear-1g.json"))
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = "kimi-linear-1g.fused-s8192"


def reader():
    return load_module(os.path.join(BENCH, "layer_metrics", "kda_core_kernel_pct.py"), "m_kda_core_kernel_pct")


def a_run():
    traffic = {"batch": 2, "seq": 8192, "steps_per_unit": 5}
    return measure.Run({}, CONFIG, traffic, PEAKS, [{"group": 0, "trace": {"xplane": "no such file"}}])


def by_names(rows):
    return lambda run, parent, names, rows_of=None: rows[(parent, tuple(names))]


@pytest.mark.parametrize("kernel_s, core_s, want", [
    (0.15, 0.16, 93.75),  # the cond, its predicate and the views around the calls are the core's, not the kernels'
    (0.05, 0.40, 12.5),   # most blocks took the exact path
])
def test_the_share_on_rows_written_by_hand(monkeypatch, kernel_s, core_s, want):
    rows = {
        ("attn", ("kda_kernel",)): {"kda_kernel": kernel_s, "attn": 0.5},
        ("attn", hybrid_scopes.CORES): {"kda_core": core_s, "mla_core": 0.06, "attn": 0.3},
    }
    monkeypatch.setattr(subscopes, "seconds", by_names(rows))
    assert reader().compute(a_run()) == pytest.approx(want)


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    assert reader().compute(a_run()) is None  # a trace that cannot be opened
    # the parent commit: a core, and no kernel scope inside it
    rows = {
        ("attn", ("kda_kernel",)): {"attn": 0.9},
        ("attn", hybrid_scopes.CORES): {"kda_core": 0.47, "mla_core": 0.06, "attn": 0.4},
    }
    monkeypatch.setattr(subscopes, "seconds", by_names(rows))
    assert reader().compute(a_run()) is None
    # another architecture: attention without either
    rows = {("attn", ("kda_kernel",)): {"attn": 0.15}, ("attn", hybrid_scopes.CORES): {"attn": 0.15}}
    monkeypatch.setattr(subscopes, "seconds", by_names(rows))
    assert reader().compute(a_run()) is None


@pytest.mark.parametrize("path", [
    "jit(tft_fused)/jvp()/checkpoint/attn/kda/while/body/checkpoint/kda_core/cond/branch_1_fun/kda_kernel/pallas_call",
    "jit(tft_fused)/transpose(jvp())/checkpoint/rematted_computation/attn/kda/while/body/checkpoint/kda_core/kda_kernel/pallas_call",
    "jit(tft_fused)/transpose(jvp())/attn/transpose(jvp(kda))/while/body/transpose(jvp(kda_core))/cond/branch_1_fun/kda_kernel/pallas_call",
])
def test_a_kernels_op_still_counts_to_its_core_and_its_mixer(path):
    assert subscopes.innermost(path, "attn", ("kda_kernel",)) == "kda_kernel"
    assert subscopes.innermost(path, "attn", hybrid_scopes.CORES) == "kda_core"  # kda_core_device_s reads what it read
    assert subscopes.innermost(path, "attn", hybrid_scopes.MIXERS) == "kda"
    outside = path.replace("/kda_kernel/pallas_call", "/reduce_max")  # the predicate on g: the core's, no kernel's
    assert subscopes.innermost(outside, "attn", ("kda_kernel",)) == "attn"
    assert subscopes.innermost(outside, "attn", hybrid_scopes.CORES) == "kda_core"


def test_the_entry_lists_the_one_cell():
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    entry = bench["per_layer"][-1]
    mod = reader()
    assert entry == {
        "name": mod.NAME, "unit": mod.UNIT, "better": "higher", "source": mod.SOURCE,
        "layer": mod.LAYER, "moves": mod.MOVES, "workloads": [CELL],
    }
    assert (mod.NAME, mod.UNIT) == ("kda_core_kernel_pct", "%")
    moved = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert CELL in moved.get("workloads", [CELL])
