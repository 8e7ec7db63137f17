"""exchange_account.py and the ten readers built on it (PR 38), against ONE
group's trace of the four-group cell's rehearsal recorded on the four-chip v5e
host (``record_exchange_account.py``: world size 4, plane ``cma``, two rings a
step). What a reader returns is compared with the same quantity worked out here
from the raw events; the parent-shaped trace (``recorded_program_spans_v5e``,
a program without the account) must read ``None`` wherever a reader needs the
account or the new counters; and the reductions over groups are held to fake runs of four groups of which one is
slow."""

import json
import os

import pytest

import exchange_account as xa
import measure
import program_spans as ps
import reduce_trace as rt
from common import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TRACE = os.path.join(HERE, "recorded_exchange_account_v5e.xplane.pb")
PARENT = os.path.join(HERE, "recorded_program_spans_v5e.xplane.pb")  # spans, no account
OLDER = os.path.join(HERE, "recorded_v5e.xplane.pb")  # no program spans at all
CELL = "olmo1b-4g.ft-steady"
NEW = (
    "ring_neighbour_wait_s", "ring_wait_imbalance_s", "ring_pull_s", "ring_pull_gbps", "ring_reduce_s",
    "ring_unattributed_s", "exchange_pack_slowest_gbps", "exchange_pack_aliased_gb", "exchange_copied_gb",
    "exchange_first_ring_delay_s",
)
EXCHANGE_BYTES = 4 * 28832  # the rehearsal's parameters, float32 gradients
WORLD = 4


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(*paths):
    """A run as ``run.py`` hands it to a reader, one group a path."""
    results = []
    for group, path in enumerate(paths):
        result = {"group": group, "device": {"platform": "tpu"}}
        if path is not None:
            result["trace"] = {"xplane": path}
            try:
                result["trace"].update(rt.reduce_file(path, []))
            except Exception:  # a file that is gone or torn: the path alone
                pass
        results.append(result)
    return measure.Run({}, {}, {"steps_per_unit": 1}, None, results)


@pytest.fixture(scope="module")
def raw():
    """Per traced step, straight from the file: the counters' stats, the rings
    and their accounts (start, end, stats) in order, the main thread's
    ``tft.exchange`` (start, end)."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(TRACE).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                lines.append([
                    (float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name, dict(e.stats))
                    for e in line.events
                ])
    (main,) = [ln for ln in lines if any(n == "bench_unit" for _, _, n, _ in ln)]
    steps = []
    for lo, hi, _, _ in sorted(ev for ev in main if ev[2] == "bench_unit"):
        inside = lambda ln, name: sorted(
            (s, e, st) for s, e, n, st in ln if n == name and lo <= s < hi
        )
        everywhere = lambda name: sorted(ev for ln in lines for ev in inside(ln, name))
        ((_, _, counters),) = inside(main, "tft.exchange.counters")
        ((xs, xe, _),) = inside(main, "tft.exchange")
        steps.append({
            "counters": counters, "exchange": (xs, xe),
            "rings": everywhere("tft.exchange.ring"), "accounts": everywhere("tft.exchange.ring.account"),
            "packs": inside(main, "tft.exchange.pack"),
        })
    return steps


def test_the_recording_is_one_group_of_four_on_the_cma_plane(raw):
    assert len(raw) >= 3
    for step in raw:
        c = step["counters"]
        assert len(step["rings"]) == len(step["accounts"]) == len(step["packs"]) == c["buckets"] == 2
        assert c["bytes_d2h"] == c["pack_bytes"] == c["h2d_bytes"] == EXCHANGE_BYTES
        assert {a["plane"] for _, _, a in step["accounts"]} == {"cma"}
        assert {a["stripes"] for _, _, a in step["accounts"]} == {4}
        # what the native ring pulls and reduces at world 4, to the byte where
        # stripes and chunks divide evenly, within a few elements where not
        assert c["ring_pull_bytes"] == pytest.approx(2 * (WORLD - 1) / WORLD * EXCHANGE_BYTES, abs=256)
        assert c["ring_reduce_bytes"] == pytest.approx((WORLD - 1) / WORLD * EXCHANGE_BYTES, abs=128)
        assert c["ring_pump_s"] == 0 and c["ring_wait_s"] > 0 and c["ring_pull_s"] > 0
        # the step's sums are its rings' accounts
        for total, fields in (("ring_wait_s", ("desc_wait_s", "ack_wait_s")), ("ring_pull_s", ("pull_s",)),
                              ("ring_reduce_s", ("reduce_s",)), ("ring_pull_bytes", ("pull_bytes",))):
            assert c[total] == pytest.approx(sum(a[f] for _, _, a in step["accounts"] for f in fields), rel=1e-6)
        # each account follows its ring on the op thread, and fits inside it
        for (rs, re_, ring), (s, e, a) in zip(step["rings"], step["accounts"]):
            assert re_ <= s and e - s < 1e5 and a["bytes"] == ring["bytes"]
            assert sum(a[k] for k in xa.ACCOUNTED) <= a["slowest_stripe_s"] + 1e-9 <= (re_ - rs) / 1e9
        assert all(0 <= p["dst_ahead_b"] < 4096 for _, _, p in step["packs"])


def test_every_reader_gives_what_the_raw_events_give(raw):
    median = measure.median
    c = lambda key: [step["counters"][key] for step in raw]
    ring_s = [sum(e - s for s, e, _ in step["rings"]) / 1e9 for step in raw]
    named = [sum(a[k] for _, _, a in step["accounts"] for k in xa.ACCOUNTED) for step in raw]
    expect = {
        "ring_neighbour_wait_s": median(c("ring_wait_s")),
        "ring_wait_imbalance_s": 0.0,  # one group: nothing to differ from
        "ring_pull_s": median(c("ring_pull_s")),
        "ring_pull_gbps": median([b / s / 1e9 for b, s in zip(c("ring_pull_bytes"), c("ring_pull_s"))]),
        "ring_reduce_s": median(c("ring_reduce_s")),
        "ring_unattributed_s": median([r - n for r, n in zip(ring_s, named)]),
        "exchange_pack_slowest_gbps": median([b / s / 1e9 for b, s in zip(c("pack_bytes"), c("pack_s"))]),
        "exchange_pack_aliased_gb": median(c("pack_aliased_bytes")) / 1e9,
        "exchange_copied_gb": 5.25 * EXCHANGE_BYTES / 1e9,
        "exchange_first_ring_delay_s": median([(step["rings"][0][0] - step["exchange"][0]) / 1e9 for step in raw]),
    }
    assert set(expect) == set(NEW)
    run = a_run(TRACE)
    for name in NEW:
        got = reader(name).compute(run)
        tolerance = dict(rel=1e-3) if name == "exchange_copied_gb" else dict(rel=1e-9, abs=1e-15)
        assert got == pytest.approx(expect[name], **tolerance), name
        assert got > 0 or name in ("ring_wait_imbalance_s", "exchange_pack_aliased_gb"), name
    # the account closes: what the rings' spans hold is named, or unattributed
    parts = sum(reader(n).compute(run) for n in ("ring_neighbour_wait_s", "ring_pull_s", "ring_reduce_s", "ring_unattributed_s"))
    assert parts == pytest.approx(reader("exchange_ring_s").compute(run), rel=0.05)
    assert reader("exchange_first_ring_delay_s").compute(run) < median([(e - s) / 1e9 for s, e in (st["exchange"] for st in raw)])


@pytest.mark.parametrize("name", NEW)
def test_a_reader_returns_none_where_there_is_nothing_to_read(name, tmp_path):
    compute = reader(name).compute
    if name == "exchange_first_ring_delay_s":
        # it reads two spans the program has had since PR 23, so the parent's
        # side has a reading too: 2.8 ms in that recording of one group
        assert compute(a_run(PARENT)) == pytest.approx(2.806e-3, rel=1e-3)
    else:
        assert compute(a_run(PARENT)) is None  # the parent's program: spans, no account
    assert compute(a_run(OLDER)) is None  # a program without the spans
    assert compute(a_run(None)) is None  # an untraced run
    assert compute(a_run(str(tmp_path / "gone.xplane.pb"))) is None
    torn = tmp_path / "torn.xplane.pb"
    torn.write_bytes(open(TRACE, "rb").read()[:1000])
    assert compute(a_run(str(torn))) is None
    # groups that have a reading are not lost to one that has none
    assert compute(a_run(TRACE, None)) == compute(a_run(TRACE))


class FakeTrace:
    """A group's trace of three steps whose counters read ``stats`` (a value,
    or one a step) and whose rings start ``ring_at`` s into the exchange."""

    main_line = 0

    def __init__(self, ring_at=0.2, ring_s=1.0, **stats):
        self.units = [(i * 1e10, (i + 1) * 1e10) for i in range(3)]
        self.stats = {k: v if isinstance(v, list) else [v] * 3 for k, v in stats.items()}
        self.ring_s = ring_s
        self.events = []
        for lo, _ in self.units:
            self.events.append((lo + 1e9, lo + 5e9, xa.EXCHANGE, 0, {}))
            self.events.append((lo + 1e9 + ring_at * 1e9, lo + 3e9, xa.RING, 7, {}))
            self.events.append((lo + 3.5e9, lo + 4e9, xa.RING, 7, {}))

    def stat(self, name, key):
        assert name in (xa.COUNTERS, xa.ACCOUNT)
        return self.stats.get(key)

    def seconds(self, name, main_only=False):
        return [self.ring_s] * 3 if name == xa.RING else None


def four_groups(monkeypatch, traces):
    monkeypatch.setattr(ps, "load", lambda result: traces[result["group"]])
    return a_run(*[None] * len(traces))


def test_one_slow_group_of_four_shows_in_the_reductions_not_in_the_mean(monkeypatch):
    """Group 2 drew the pack's slow level: it packs at 2.4 GB/s inside the
    window, reaches its rings late and waits for nobody; the other three wait
    for it."""
    n = 2_430_000_000
    fast = dict(pack_bytes=n, pack_s=0.45, pack_aliased_bytes=0, ring_wait_s=0.8, ring_pull_s=0.25,
                ring_pull_bytes=1.5 * n, ring_reduce_s=0.1, ring_reduce_bytes=0.75 * n, bytes_d2h=n, h2d_bytes=n)
    slow = dict(fast, pack_s=1.0, pack_aliased_bytes=n, ring_wait_s=[0.05, 0.1, 0.2])
    traces = [FakeTrace(**fast), FakeTrace(**fast), FakeTrace(ring_at=0.55, **slow), FakeTrace(**fast)]
    run = four_groups(monkeypatch, traces)
    wait = xa.counter("ring_wait_s")
    assert xa.per_group(run, wait) == [0.8, 0.8, 0.1, 0.8]  # per group the median of its steps
    assert xa.over_groups(run, wait, "mean") == pytest.approx(0.625)
    assert xa.over_groups(run, wait, "max") == 0.8
    assert xa.over_groups(run, wait, "min") == 0.1
    assert xa.over_groups(run, wait, "spread") == pytest.approx(0.7)
    assert reader("ring_neighbour_wait_s").compute(run) == pytest.approx(0.625)
    assert reader("ring_wait_imbalance_s").compute(run) == pytest.approx(0.7)
    assert reader("exchange_pack_slowest_gbps").compute(run) == pytest.approx(2.43)
    assert reader("exchange_pack_aliased_gb").compute(run) == pytest.approx(2.43)
    assert reader("exchange_copied_gb").compute(run) == pytest.approx(5.25 * 2.43)
    assert reader("ring_pull_gbps").compute(run) == pytest.approx(1.5 * 2.43 / 0.25)
    assert reader("exchange_first_ring_delay_s").compute(run) == pytest.approx(0.55)
    # a group without a reading drops out of every reduction; none at all is None
    traces[0] = FakeTrace()
    assert xa.per_group(run, wait)[0] is None
    assert xa.over_groups(run, wait, "spread") == pytest.approx(0.7)
    assert xa.over_groups(run, wait, "mean") == pytest.approx((0.8 + 0.1 + 0.8) / 3)
    assert xa.over_groups(four_groups(monkeypatch, [FakeTrace()] * 4), wait, "max") is None


def test_what_the_account_does_not_name_is_the_rings_rest(monkeypatch):
    named = dict(desc_wait_s=0.3, ack_wait_s=0.2, pull_s=0.25, reduce_s=0.1, pump_s=0.0, codec_s=0.0)
    run = four_groups(monkeypatch, [FakeTrace(ring_s=1.0, **named), FakeTrace(ring_s=0.9, **named)])
    assert reader("ring_unattributed_s").compute(run) == pytest.approx((0.15 + 0.05) / 2)
    # plane tcp: nothing is pulled, so the pull has no rate, and no error
    tcp = dict(ring_pull_bytes=0, ring_pull_s=0.0, ring_wait_s=0.0)
    run = four_groups(monkeypatch, [FakeTrace(**tcp)] * 4)
    assert reader("ring_pull_gbps").compute(run) is None
    assert reader("ring_neighbour_wait_s").compute(run) == 0.0  # an exact 0 is a reading
    # a step without a ring has no first ring
    empty = FakeTrace()
    empty.events = [ev for ev in empty.events if ev[2] != xa.RING]
    assert xa.first_ring_delay(empty) is None


def test_the_ten_entries_name_the_cell_and_their_readers_files():
    bench = json.load(open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")))
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-10:]] == list(NEW)  # appended, in the issue's order
    for name in NEW:
        entry, mod = listed[name], reader(name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "step_p50_s"
        assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER) == (name, entry["unit"], entry["source"], entry["layer"])
        assert entry["better"] == ("higher" if name.endswith("_gbps") else "lower")
