"""``attn_core_recomputed_device_s`` (PR 47): device seconds a step of the flash kernel's
forward under ``rematted_computation`` — on paths and rows written by hand, through
``subscopes.units``' own rules on a trace written by hand, where there is nothing to
read, and in ``BENCHMARK.json``."""

import os

import pytest

import measure
import subscopes
from common import load_json, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

# what the chip's compiler gives the kernel's calls (``jit(tft_fused)``, a described v5e): the scanned layer's …
FIRST = "jit(tft_fused)/jvp()/while/body/closed_call/attn/core/flash_fwd/pallas_call"
AGAIN = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attn/core/flash_fwd/pallas_call"
BACKWARD = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/checkpoint/attn/core/flash_bwd/pallas_call"
# … a pattern's mixer under its own checkpoint, and the multi-token-prediction module's
AGAIN_IN_A_MIXER = "jit(tft_fused)/transpose(jvp())/checkpoint/rematted_computation/attn/mla/mla_core/flash_fwd/pallas_call"
AGAIN_IN_THE_MODULE = "jit(tft_fused)/transpose(jvp())/checkpoint/rematted_computation/attn/mtp/mla/mla_core/flash_fwd/pallas_call"
PROJECTION_AGAIN = "jit(tft_fused)/transpose(jvp())/checkpoint/rematted_computation/attn/mla/q_lora/dot_general"
ANOTHER_SCOPE = "jit(tft_fused)/transpose(jvp())/checkpoint/rematted_computation/ffn/dot_general"


def reader():
    return load_module(os.path.join(BENCH, "layer_metrics", "attn_core_recomputed_device_s.py"), "m_attn_core_recomputed_device_s")


def a_run(xplane="no such file"):
    traffic = {"batch": 2, "seq": 8192, "steps_per_unit": 5}
    return measure.Run({}, {}, traffic, None, [{"group": 0, "trace": {"xplane": xplane}}])


@pytest.mark.parametrize("path, want", [
    (AGAIN, "flash_fwd_again"), (AGAIN_IN_A_MIXER, "flash_fwd_again"), (AGAIN_IN_THE_MODULE, "flash_fwd_again"),
    (FIRST, "attn"), (BACKWARD, "attn"), (PROJECTION_AGAIN, "attn"),  # the scope's, and no second forward of the kernel
    (ANOTHER_SCOPE, None), (None, None), ("jit(tft_fused)/while/body/copy", None),
])
def test_which_op_is_the_kernels_forward_again(path, want):
    assert reader().again(path) == want


def test_the_seconds_through_the_units_own_rules_on_a_trace_written_by_hand(monkeypatch):
    """Two traced units of one program run each (the last is left out): a
    first forward, the forward again, a backward, an op of another scope, and
    in a run launched before the trace began a forward again that is no unit's."""
    ms = 1e6  # the trace's clock is in ns
    names = {(7, "%flash_fwd.1"): FIRST, (7, "%flash_fwd.2"): AGAIN, (7, "%flash_bwd.1"): BACKWARD, (7, "%fusion.3"): ANOTHER_SCOPE}
    ops, modules, enqueued = [], [], {}
    for run, start in ((1, 0), (2, 100 * ms), (3, 200 * ms)):
        modules.append((start, start + 90 * ms, "jit_tft_fused(7)", run))
        ops += [
            (start, start + 13 * ms, "%flash_fwd.1"), (start + 20 * ms, start + 30 * ms, "%fusion.3"),
            (start + 40 * ms, start + 53 * ms, "%flash_fwd.2"), (start + 60 * ms, start + 86 * ms, "%flash_bwd.1"),
        ]
    enqueued.update({2: 95 * ms, 3: 195 * ms})  # run 1 was launched before the trace began
    trace = ([(ops, sorted(modules), names)], [90 * ms, 190 * ms], enqueued)
    monkeypatch.setattr(subscopes, "_trace", lambda path: trace)
    mod = reader()
    rows = mod.rows("a trace")
    assert rows == [pytest.approx({"flash_fwd_again": 0.013, "attn": 0.039})] * 2
    assert subscopes.innermost(AGAIN, "attn", ("flash_fwd",)) == "flash_fwd"  # the reader's rule was lent, not left behind
    assert mod.compute(a_run()) == pytest.approx(0.013 / 5)


def test_rows_written_by_hand_and_where_there_is_nothing_to_read(monkeypatch):
    mod = reader()
    assert mod.compute(a_run()) is None  # a trace that cannot be opened
    assert mod.compute(measure.Run({}, {}, {"steps_per_unit": 5}, None, [{"group": 0}])) is None  # an untraced run
    by_path = {
        "the parent": [{"flash_fwd_again": 0.395, "attn": 2.2}, {"flash_fwd_again": 0.393, "attn": 2.2}, {"attn": 0.1}],
        "the change": [{"attn": 2.2}, {"attn": 2.2}, {"attn": 0.1}],  # the kernel's forward runs once: an exact 0
        "no attention on the device": [{}, {}],
    }
    monkeypatch.setattr(mod, "rows", by_path.get)
    assert mod.compute(a_run("the parent")) == pytest.approx(0.394 / 5)
    assert mod.compute(a_run("the change")) == 0.0
    assert mod.compute(a_run("no attention on the device")) is None


def test_the_entry_names_no_cell_and_every_cell_reports_what_it_moves():
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    mod = reader()
    entry = next(m for m in bench["per_layer"] if m["name"] == mod.NAME)
    assert entry == {
        "name": "attn_core_recomputed_device_s", "unit": "s", "better": "lower", "source": "device_trace",
        "layer": "device compute", "moves": "step_p50_s",
    }
    assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (entry["unit"], entry["source"], entry["layer"], entry["moves"])
    moved = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert "workloads" not in moved  # every cell runs the kernel and reports step_p50_s
