"""The scope readers (``layer_metrics/<scope>_device_s.py``, ``<scope>_roofline.py``)
on the trace recorded with scopes on the v5e, and on rows written by hand;
``exchange_buckets_reused`` on a trace object that carries the counter."""

import os

import pytest

import measure
import opcount
import program_spans as ps
import reduce_trace as rt
import scope_metrics
from common import load_json, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SCOPED = os.path.join(HERE, "recorded_scopes_v5e.xplane.pb")
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
SCOPE_READERS = (
    "attn_device_s", "ffn_device_s", "head_loss_device_s", "optimizer_device_s", "embed_device_s",
    "unscoped_device_s", "attn_roofline", "ffn_roofline", "head_loss_roofline", "optimizer_roofline",
)


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(units, tc, steps_per_unit=1, peaks=PEAKS, groups=1, chips_per_group=1):
    config = {"program": {"transformer_config": tc}, "layout": {"groups": groups, "chips_per_group": chips_per_group}}
    traffic = {"batch": 8, "seq": 2048, "steps_per_unit": steps_per_unit}
    results = [{"group": g, "trace": {"units": units}} for g in range(groups)]
    return measure.Run({}, config, traffic, peaks, results)


def olmo_tc():
    return load_json(os.path.join(BENCH, "configs", "olmo1b-1g.json"))["program"]["transformer_config"]


def test_the_readers_on_the_recorded_trace():
    """One step a unit: the median of the first two of the three units' rows."""
    units = rt.reduce_file(SCOPED, [])["units"]
    run = a_run(units, olmo_tc())
    # a miniature: the gaps between its ops are 0.6 % of the program run (0.002-0.012 % at a cell's size),
    # which the readers would take for a unit whose scopes do not add up
    assert "scopes add up to" in scope_metrics.fault(run)
    run = a_run([{k: v for k, v in u.items() if k != "programs"} for u in units], olmo_tc())
    got = {name: reader(name).compute(run) for name in SCOPE_READERS}
    assert got["attn_device_s"] == pytest.approx((36684 + 36685) / 2 * 1e-9, rel=1e-9)
    assert got["ffn_device_s"] == pytest.approx((131679 + 131614) / 2 * 1e-9, rel=1e-9)
    assert got["head_loss_device_s"] == pytest.approx(36111e-9, rel=1e-9)
    assert got["optimizer_device_s"] == pytest.approx((14956 + 14917) / 2 * 1e-9, rel=1e-9)
    assert got["unscoped_device_s"] == pytest.approx((13073 + 13012) / 2 * 1e-9, rel=1e-9)
    assert got["embed_device_s"] is None  # the recorded step has no such scope: left out, not 0


def test_shares_of_the_peaks_worked_by_hand():
    """The fused cell's numbers of PR 25's first chip run: five steps a unit."""
    tc = olmo_tc()
    row = {"attn": 1.06567255, "ffn": 1.06661835, "head_loss": 0.493307089, "optimizer": 0.116583527,
           "embed": 0.025150404, "unscoped": 0.157432779}
    run = a_run([{"by_scope": row}], tc, steps_per_unit=5)
    tokens = 8 * 2048
    assert reader("ffn_device_s").compute(run) == pytest.approx(1.06661835 / 5)
    # ffn: 6 x (6 layers x 3 x 2048 x 8192) operations a token
    assert reader("ffn_roofline").compute(run) == pytest.approx(
        100 * 6 * 6 * 3 * 2048 * 8192 * tokens / (1.06661835 / 5 * 197e12)) == pytest.approx(70.64, abs=0.01)
    # attn: the projections, and causal scores at half the square, three times with the backward
    attn = 6 * 6 * 4 * 2048 * 2048 + 3 * 6 * 2 * (2 * 2048 * 2048) / 2
    assert reader("attn_roofline").compute(run) == pytest.approx(100 * attn * tokens / (1.06567255 / 5 * 197e12))
    assert reader("head_loss_roofline").compute(run) == pytest.approx(
        100 * 6 * 2048 * 50304 * tokens / (0.493307089 / 5 * 197e12)) == pytest.approx(52.11, abs=0.01)
    # optimizer: 24 B of f32 state and a bf16 gradient for each of 608 724 992 parameters against 819 GB/s
    assert reader("optimizer_roofline").compute(run) == pytest.approx(
        100 * 26 * 608_724_992 / (0.116583527 / 5 * 819e9)) == pytest.approx(82.88, abs=0.01)
    # a group sharded over two chips does half the work on each
    two = a_run([{"by_scope": row}], tc, steps_per_unit=5, chips_per_group=2)
    assert reader("ffn_roofline").compute(two) == pytest.approx(70.64 / 2, abs=0.01)


def test_the_feed_forward_readers_follow_the_configurations_scope():
    moe = dict(olmo_tc(), n_layers=1, d_ff=1024, n_experts=64, top_k=8)
    run = a_run([{"by_scope": {"moe": 0.2, "attn": 0.1}}], moe)
    assert reader("ffn_device_s").compute(run) == 0.2
    assert reader("ffn_roofline").compute(run) == pytest.approx(
        100 * opcount.flops_per_token_by_scope(moe, 2048)["moe"] * 8 * 2048 / (0.2 * 197e12))
    assert reader("ffn_device_s").compute(a_run([{"by_scope": {"moe": 0.2}}], olmo_tc())) is None


# a count as an architecture with a dense layer ahead of its expert layers brings it (``opcounts/<name>.py``):
# both feed-forward scopes, ``ffn`` bound by its operations and ``moe`` by its bytes at these made-up numbers
BOTH = """
def n_params(tc):
    return 1000

def flops_per_token_by_scope(tc, seq):
    return {"attn": 4.0e9, "ffn": 1.97e9, "moe": 0.197e9, "head_loss": 1.0e9}

def bytes_per_step_by_scope(tc, batch, seq):
    return {"attn": 1e9, "ffn": 0.819e9, "moe": 81.9e9, "head_loss": 1e9, "optimizer": 26000}

def ffn_scopes(tc):
    return ("ffn", "moe")
"""


def test_a_configuration_with_a_dense_layer_ahead_of_its_expert_layers_reads_both(tmp_path, monkeypatch):
    monkeypatch.setattr(opcount, "OPCOUNTS_DIR", str(tmp_path))
    (tmp_path / "both.py").write_text(BOTH)
    units = [{"by_scope": {"ffn": 0.5, "moe": 1.5, "attn": 1.0, "optimizer": 0.1}}]
    run = a_run(units, olmo_tc(), steps_per_unit=5)
    run.config["program"]["opcount"] = "both"
    assert reader("ffn_device_s").compute(run) == pytest.approx((0.5 + 1.5) / 5)
    # 16 384 tokens: ffn needs 1.97e9 x 16 384 / 197e12 = 0.16384 s (its bytes 0.001 s), moe 81.9e9 / 819e9 = 0.1 s
    # (its operations 0.016384 s): each scope its own nearer bound, one after the other
    assert reader("ffn_roofline").compute(run) == pytest.approx(100 * (0.16384 + 0.1) / 0.4)
    assert reader("attn_roofline").compute(run) == pytest.approx(100 * 4.0e9 * 16384 / 197e12 / 0.2)
    assert reader("optimizer_roofline").compute(run) == pytest.approx(100 * 26000 / 819e9 / 0.02)
    assert reader("mfu_pct").compute(a_run(units, olmo_tc(), peaks=None)) is None
    # one of the two ran no op (a program that names its dense layer otherwise): the seconds are the other's,
    # and no share is given of a least time that counts both
    run = a_run([{"by_scope": {"moe": 1.5, "attn": 1.0}}], olmo_tc(), steps_per_unit=5)
    run.config["program"]["opcount"] = "both"
    assert reader("ffn_device_s").compute(run) == pytest.approx(1.5 / 5)
    assert reader("ffn_roofline").compute(run) is None
    # without the key the same rows are opcount.py's dense configuration: ``ffn`` alone
    plain = a_run(units, olmo_tc(), steps_per_unit=5)
    assert reader("ffn_device_s").compute(plain) == pytest.approx(0.5 / 5)


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_a_scope_reader_returns_none_where_there_is_nothing_to_read(name):
    compute = reader(name).compute
    tc = olmo_tc()
    assert compute(measure.Run({}, {"program": {"transformer_config": tc}, "layout": {"chips_per_group": 1}},
                               {"batch": 8, "seq": 2048, "steps_per_unit": 1}, PEAKS, [{"group": 0}])) is None  # untraced
    assert compute(a_run([{"dur_s": 1.0, "busy_s": 0.5}], tc)) is None  # a trace reduced without scopes
    if name.endswith("_roofline"):
        row = {"attn": 0.2, "ffn": 0.2, "head_loss": 0.1, "optimizer": 0.02}
        assert compute(a_run([{"by_scope": row}], tc, peaks=None)) is None  # a device without published peaks


def test_the_last_of_several_traced_units_is_left_out():
    """Its ``apply`` may be cut where the trace stops: with two units, or two cut ones, a median would take it in."""
    units = [{"by_scope": {"optimizer": 0.0254}}, {"by_scope": {"optimizer": 0.0256}}, {"by_scope": {"optimizer": 0.0021}}]
    assert reader("optimizer_device_s").compute(a_run(units, olmo_tc())) == pytest.approx(0.0255)
    assert reader("optimizer_device_s").compute(a_run(units[1:], olmo_tc())) == 0.0256
    assert reader("optimizer_device_s").compute(a_run(units[2:], olmo_tc())) == 0.0021  # one unit: its loop blocks
    # two groups: the mean of their medians
    units2 = [{"by_scope": {"optimizer": 0.03}}]
    run = a_run(units, olmo_tc(), groups=2)
    run.results[1]["trace"]["units"] = units2
    assert reader("optimizer_device_s").compute(run) == pytest.approx((0.0255 + 0.03) / 2)


def test_buckets_reused_is_the_counters_median(monkeypatch):
    class Counters:
        units = [(0.0, 1.0), (1.0, 2.0), (2.0, 3.0)]

        def __init__(self, values):
            self.values = values

        def stat(self, name, key):
            assert (name, key) == (ps.PREFIX + "exchange.counters", "buckets_reused")
            return self.values

    run = measure.Run({}, {}, {}, None, [{"group": 0}])
    compute = reader("exchange_buckets_reused").compute
    monkeypatch.setattr(ps, "load", lambda result: Counters([10.0, 10.0, 0.0]))
    assert compute(run) == 10.0
    monkeypatch.setattr(ps, "load", lambda result: Counters([0.0, 0.0, 0.0]))
    assert compute(run) == 0.0  # nothing kept is a reading
    monkeypatch.setattr(ps, "load", lambda result: Counters(None))
    assert compute(run) is None  # a program that does not count it


def test_pages_kept_is_the_counters_median(monkeypatch):
    class Counters:
        units = [(0.0, 1.0), (1.0, 2.0), (2.0, 3.0)]

        def __init__(self, values):
            self.values = values

        def stat(self, name, key):
            assert (name, key) == (ps.PREFIX + "exchange.counters", "d2h_pages_kept")
            return self.values

    run = measure.Run({}, {}, {}, None, [{"group": 0}])
    compute = reader("exchange_d2h_pages_kept").compute
    monkeypatch.setattr(ps, "load", lambda result: Counters([1.0, 1.0, 1.0]))
    assert compute(run) == 1.0
    monkeypatch.setattr(ps, "load", lambda result: Counters([0.0, 0.0, 1.0]))
    assert compute(run) == 0.0  # fresh pages on most steps is a reading
    monkeypatch.setattr(ps, "load", lambda result: Counters(None))
    assert compute(run) is None  # a program from before PR 27 does not count it


def test_the_host_probe_reader_takes_the_copy_at_the_windows_open():
    compute = reader("host_probe_copy_s").compute
    probe = lambda copy: {"open": {"copy_s": copy, "spin_s": 0.04}, "close": {"copy_s": 9.0, "spin_s": 0.05}}
    run = measure.Run({}, {}, {}, None, [{"group": 0, "host_probe": probe(0.024)}, {"group": 1, "host_probe": probe(0.030)}])
    assert compute(run) == pytest.approx(0.027)  # the mean of the groups', never the close's
    assert compute(measure.Run({}, {}, {}, None, [{"group": 0}])) is None  # a worker from before the probe


# -- the scopes of a unit add up to its program runs, or no scope is reported

def pr28_units():
    """The traced run of the ledger's PR 28 line (change's side), as the rows of then: the first unit was given 4.3 ms
    of the ``apply`` that began before the trace did, so ``optimizer_device_s`` read 0.014749 s and its roofline 131.02 %.
    ``reduce_trace`` now keeps such ops out of every unit (``unlaunched_s``); these rows are what a reduction that
    does not would hand the readers."""
    full = {"attn": 0.21278, "ffn": 0.2134, "head_loss": 0.098626, "optimizer": 0.025195, "embed": 0.0060976, "unscoped": 0.035978}
    programs = {"jit_tft_grads": 0.56677, "jit_tft_apply": 0.025441, "jit_convert_element_type": 6e-7}
    cut = dict(full, optimizer=0.004303)
    return [{"by_scope": cut, "programs": {k: v for k, v in programs.items() if k != "jit_tft_apply"}},
            {"by_scope": full, "programs": programs}, {"by_scope": full, "programs": programs}]


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_no_scope_is_reported_where_a_units_scopes_do_not_add_up(name, capsys):
    compute = reader(name).compute
    units = pr28_units()
    sound = [units[1], units[1], units[0]]  # the cut unit last: left out, as every last unit is
    assert compute(a_run(sound, olmo_tc())) is not None
    if name == "optimizer_roofline":
        # what the readers printed before they checked: the median of a cut and a whole unit
        unchecked = [{"by_scope": u["by_scope"]} for u in units]
        assert compute(a_run(unchecked, olmo_tc())) == pytest.approx(131.0, abs=0.1)
    assert compute(a_run(units, olmo_tc())) is None  # 0.76 % over what the unit's runs took
    assert "scope metrics left out: group 0, traced unit 0: the scopes add up to 0.571" in capsys.readouterr().out
    # the other way: the scopes lack 1.8 % of the runs
    lacking = [dict(units[1], by_scope=dict(units[1]["by_scope"], optimizer=0.014749)), units[1]]
    assert compute(a_run(lacking, olmo_tc())) is None
    assert "the scopes add up to 0.58" in capsys.readouterr().out
    # 0.012 % apart, as the cells read (PERF.md section 5), is sound
    near = [dict(units[1], by_scope=dict(units[1]["by_scope"], unscoped=0.035978 - 7e-5)), units[1]]
    assert compute(a_run(near, olmo_tc())) is not None
