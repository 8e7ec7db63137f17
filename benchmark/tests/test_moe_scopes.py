"""The three readers inside the scope ``moe`` (``moe_experts_device_s``,
``moe_route_device_s``, ``moe_experts_roofline``) and their helper
``moe_scopes.py``: on the trace of the program's own small dropless-expert step
recorded on the v5e (``record_moe_trace.py``), on rows written by hand, and
where there is nothing to read."""

import os

import pytest

import measure
import moe_scopes
import opcount
import reduce_trace as rt
import subscopes
from common import load_json, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
RECORDED = os.path.join(HERE, "recorded_moe_v5e.xplane.pb")
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
READERS = ("moe_experts_device_s", "moe_route_device_s", "moe_experts_roofline")


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def olmoe_tc():
    return load_json(os.path.join(BENCH, "configs", "olmoe-1g.json"))["program"]["transformer_config"]


def a_run(xplane, tc, steps_per_unit=1, peaks=PEAKS):
    config = {"program": {"transformer_config": tc}, "layout": {"groups": 1, "chips_per_group": 1}}
    traffic = {"batch": 8, "seq": 2048, "steps_per_unit": steps_per_unit}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": xplane}}])


def test_the_innermost_of_the_four_on_a_path_through_moe():
    sub = moe_scopes.subscope_of
    assert sub("jit(tft_fused)/jvp()/while/body/closed_call/checkpoint/moe/experts/ragged_dot") == "experts"
    assert sub("jit(tft_fused)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/moe/dispatch/sort") == "dispatch"
    assert sub("jit(tft_grads)/transpose(jvp(moe))/transpose(jvp(combine))/gather") == "combine"
    assert sub("jit(tft_fused)/jvp()/while/body/closed_call/checkpoint/moe/router/dot_general") == "router"
    # an op of moe that names none of the four: the norm before the router, the residual add
    assert sub("jit(tft_fused)/jvp()/while/body/closed_call/checkpoint/moe/mul") == "moe"
    # not moe's: another scope, no scope, a dispatch that is not under moe, no op_name at all
    assert sub("jit(tft_fused)/jvp()/while/body/closed_call/checkpoint/attn/dot_general") is None
    assert sub("jit(tft_fused)/optimizer/add") is None and sub("jit(f)/dispatch/sort") is None
    assert sub(None) is None and sub("") is None


def test_the_recorded_step_splits_its_moe_seconds_among_the_four():
    """The program's own small step on the v5e, one step a unit, three units."""
    rows = moe_scopes._units_by_subscope(RECORDED)
    assert [{k: round(v * 1e9) for k, v in row.items()} for row in rows] == [
        {"moe": 4690, "router": 4341, "dispatch": 57062, "experts": 112877, "combine": 52602},
        {"moe": 4356, "router": 4291, "dispatch": 57028, "experts": 112587, "combine": 52796},
        {"moe": 4636, "router": 4280, "dispatch": 57056, "experts": 112095, "combine": 52590},
    ]
    # the split of what ``ffn_device_s`` reads, nothing lost and nothing added
    whole = [u["by_scope"]["moe"] for u in rt.reduce_file(RECORDED, [])["units"]]
    assert [sum(row.values()) for row in rows] == pytest.approx(whole, rel=1e-9)
    # the grouped matmuls are the Pallas kernel, and it carries the scope (XLA's own ragged-dot kernel does not)
    import xplane_meta

    kernels = {
        name.split(" = ")[0]: op for (_, name), op in xplane_meta.op_names(RECORDED)["/device:TPU:0"].items()
        if "custom-call" in name and moe_scopes.subscope_of(op) == "experts"
    }
    assert len(kernels) == 12 and all(op.endswith("/pallas_call") for op in kernels.values())  # 3 forward, 3 again, 6 backward
    assert sum(k.startswith("%tgmm") for k in kernels) == 3 and sum(k.startswith("%gmm") for k in kernels) == 9
    # the readers: the median of the first two units (the last of several is left out)
    tc = dict(olmoe_tc(), d_model=256, d_ff=256, n_experts=8, top_k=2, vocab_size=512, n_heads=2)
    run = a_run(RECORDED, tc)
    run.traffic.update(batch=4, seq=256)
    assert reader("moe_experts_device_s").compute(run) == pytest.approx((112877 + 112587) / 2 * 1e-9, rel=1e-6)
    assert reader("moe_route_device_s").compute(run) == pytest.approx((231572 - 112877 + 231058 - 112587) / 2 * 1e-9, rel=1e-6)
    # at this size the bytes are the nearer bound: 8 experts' weights three times and five [1024, 256] activations, in bf16
    flops, moved = 6 * 2 * 3 * 256 * 256 * 4 * 256, 3 * 2 * 8 * 3 * 256 * 256 + 5 * 1024 * 256 * 2
    assert moved / 819e9 > flops / 197e12
    assert reader("moe_experts_roofline").compute(run) == pytest.approx(100 * moved / 819e9 / 112732e-9, rel=1e-4)


def test_the_experts_least_work_counted_by_hand():
    """OLMoE's widths, one layer, b8 x s2048: 16 384 tokens, each through 8 experts of three 2048 x 1024 matrices."""
    tc = olmoe_tc()
    tokens = 8 * 2048
    assert moe_scopes.experts_flops_per_step(tc, 8, 2048) == 6 * 8 * 3 * 2048 * 1024 * tokens == pytest.approx(4.948e12, rel=1e-4)
    # what opcount counts for the scope is that and the router's 2048 x 64 matmul
    assert opcount.flops_per_token_by_scope(tc, 2048)["moe"] * tokens - moe_scopes.experts_flops_per_step(tc, 8, 2048) == 6 * 2048 * 64 * tokens
    # bytes: 64 experts' weights read twice and their gradient written, in bf16; five [tokens, 2048] activations at the edge
    assert moe_scopes.experts_bytes_per_step(tc, 8, 2048) == 3 * 2 * 64 * 3 * 2048 * 1024 + 5 * tokens * 2048 * 2 == 2_751_463_424
    # the operations are the nearer bound: 25.1 ms against 3.4 ms
    assert 4.948e12 / PEAKS["bf16_flops_per_s"] > 7 * 2_751_463_424 / PEAKS["hbm_bytes_per_s"]


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    """Two whole traced units and a cut one, five steps a unit."""
    rows = [
        {"experts": 0.25, "router": 0.01, "dispatch": 0.05, "combine": 0.03, "moe": 0.01},
        {"experts": 0.27, "router": 0.01, "dispatch": 0.05, "combine": 0.03, "moe": 0.01},
        {"experts": 0.02},
    ]
    monkeypatch.setattr(moe_scopes, "_units_by_subscope", lambda path: rows)
    run = a_run("somewhere.xplane.pb", olmoe_tc(), steps_per_unit=5)
    assert reader("moe_experts_device_s").compute(run) == pytest.approx(0.26 / 5)
    assert reader("moe_route_device_s").compute(run) == pytest.approx(0.10 / 5)
    assert reader("moe_experts_roofline").compute(run) == pytest.approx(100 * 6 * 8 * 3 * 2048 * 1024 * 8 * 2048 / 197e12 / (0.26 / 5))
    assert reader("moe_experts_roofline").compute(a_run("x", olmoe_tc(), 5, peaks=None)) is None  # no published peaks


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_none_where_there_is_nothing_to_read(name):
    compute = reader(name).compute
    dense = load_json(os.path.join(BENCH, "configs", "olmo1b-1g.json"))["program"]["transformer_config"]
    assert compute(a_run(None, olmoe_tc())) is None  # an untraced run
    assert compute(a_run(os.path.join(HERE, "no_such.xplane.pb"), olmoe_tc())) is None  # the file is gone
    # a dense model's trace (PR 25's recording) has no op of the scope; nor has its configuration experts
    assert compute(a_run(os.path.join(HERE, "recorded_scopes_v5e.xplane.pb"), dense)) is None
    assert compute(measure.Run({}, {"program": {"transformer_config": dense}, "layout": {"chips_per_group": 1}},
                               {"batch": 8, "seq": 2048, "steps_per_unit": 1}, PEAKS, [{"group": 0}])) is None


def test_a_scope_split_into_names_it_does_not_carry_stays_whole():
    """``subscopes.py`` on another parent: PR 25's dense recording nests nothing under ``attn``, so a split of it
    into two kinds of sequence mixing gives every op of the scope to ``attn`` itself — what ``attn_device_s`` reads."""
    scoped = os.path.join(HERE, "recorded_scopes_v5e.xplane.pb")
    rows = subscopes.units(scoped, "attn", ("kda", "mla"))
    whole = [u["by_scope"]["attn"] for u in rt.reduce_file(scoped, [])["units"]]
    assert [set(row) for row in rows] == [{"attn"}] * 3
    assert [row["attn"] for row in rows] == pytest.approx(whole, rel=1e-9)
    assert subscopes.innermost("jit(f)/jvp()/while/body/attn/kda/dot_general", "attn", ("kda", "mla")) == "kda"
    assert subscopes.innermost("jit(f)/transpose(jvp(attn))/transpose(jvp(mla))/core/dot_general", "attn", ("kda", "mla")) == "mla"
    assert subscopes.innermost("jit(f)/jvp()/while/body/moe/kda/dot_general", "attn", ("kda", "mla")) is None
    # the readers' reduction: the median of the first two units, and nothing where the trace is gone
    run = a_run(scoped, olmoe_tc())
    assert subscopes.seconds(run, "attn", ("kda", "mla")) == {"attn": pytest.approx((whole[0] + whole[1]) / 2, rel=1e-9)}
    assert subscopes.seconds(a_run(os.path.join(HERE, "no_such.xplane.pb"), olmoe_tc()), "attn", ("kda", "mla")) is None
