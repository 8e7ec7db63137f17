"""reduce_trace.py against traces recorded on one TPU v5e chip
(``record_trace.py``): three annotated units of a jitted 3-iteration scan.
The expected numbers were worked by hand from the events of the file.

The device plane's clock runs ~1.3 ms ahead of the host's here, so the
first program (39.967 ms) falls before the first unit (41.226 ms) and the
third unit holds none: the window holds programs two and three."""

import os

import pytest

import reduce_trace as rt

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "recorded_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return rt.reduce_file(TRACE, ["ft.grads", "ft.exchange"])


def test_window_and_busy(reduced):
    assert reduced["chips"] == 1
    # first unit starts 41226430 ns, last ends 47852390 + 3202770 ns
    assert reduced["window_s"] == pytest.approx(9.82873e-3, rel=1e-9)
    # program 2: ops from 43402463 to 43445916 ns with one 2 ns hole = 43451;
    # program 3: 46534131 to 46577583 ns with two 1 ns holes = 43450
    assert reduced["busy_s"] == pytest.approx((43451 + 43450) / 1e9, rel=1e-9)
    assert [round(u["busy_s"] * 1e9) for u in reduced["units"]] == [43451, 43450, 0]
    assert reduced["units"][0]["dur_s"] == pytest.approx(3.34939e-3, rel=1e-9)


def test_op_time_is_self_time_under_short_names(reduced):
    ops = dict(reduced["device_ops"])
    # six runs of the scan body's matmul fusion inside the window
    assert ops["%fusion.8 bf16[1024,1024]"] == pytest.approx(69437e-9, rel=1e-9)
    # the while spans its body: 36356 ns each, of which 55 and 57 ns its own
    assert ops["%while s32[]"] == pytest.approx(112e-9, rel=1e-6)
    assert all(len(name) <= 80 for name in ops)
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"], rel=1e-6)


def test_gaps_go_to_the_innermost_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) == {"ft.exchange", "ft.grads", "step, outside the wrapped calls", "outside any span"}
    assert gaps["ft.exchange"] > gaps["ft.grads"] > gaps["outside any span"]
    # busy + idle = the window
    assert sum(gaps.values()) + reduced["busy_s"] == pytest.approx(reduced["window_s"], rel=1e-9)


def test_self_times_and_union():
    assert rt._self_times([(0, 100, "while"), (10, 30, "a"), (40, 60, "a"), (100, 120, "b")]) == pytest.approx(
        {"while": 60e-9, "a": 40e-9, "b": 20e-9}
    )
    assert rt.union_seconds([(0, 10), (5, 20), (30, 40)], 8, 35) == pytest.approx(17e-9)
    assert rt.short_op_name("%fusion.8 = bf16[1024,1024]{1,0:T(8,128)} fusion(x)") == "%fusion.8 bf16[1024,1024]"
    assert rt.short_op_name("%t = (f32[8,256]{1,0}, bf16[2]) fusion(x)") == "%t f32[8,256]"


def test_a_trace_without_units_or_device_is_an_error():
    with pytest.raises(ValueError, match="bench_unit"):
        rt.reduce_planes([], ["ft.grads"])


# -- device time by named scope: a second trace recorded on the v5e
# (``record_trace.py <dir> scopes``): three units of a training step in
# miniature, a scan over three checkpointed layers with ``attn`` and ``ffn``
# scopes, a ``head_loss``, its gradient, and an ``optimizer`` update.

SCOPED = os.path.join(HERE, "recorded_scopes_v5e.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    return rt.reduce_file(SCOPED, ["ft.grads", "ft.exchange"])


def test_scopes_and_unscoped_add_up_to_the_busy_time(scoped):
    by_scope = scoped["by_scope"]
    assert set(by_scope) == {"attn", "ffn", "head_loss", "optimizer", "unscoped"}
    assert all(v > 0 for v in by_scope.values())
    # every op is in exactly one scope: the same trace's device-busy seconds
    assert sum(by_scope.values()) == pytest.approx(scoped["busy_s"], rel=1e-9)
    # each unit blocks on its program, so the units own all of it between them
    for scope, sec in by_scope.items():
        assert sum(u["by_scope"].get(scope, 0.0) for u in scoped["units"]) == pytest.approx(sec, rel=1e-9)
    for u in scoped["units"]:
        assert sum(u["by_scope"].values()) == pytest.approx(u["busy_s"], rel=1e-9)
    # the first unit's program, in ns as recorded: 232503 busy
    assert {k: round(v * 1e9) for k, v in scoped["units"][0]["by_scope"].items()} == {
        "attn": 36684, "ffn": 131679, "head_loss": 36111, "optimizer": 14956, "unscoped": 13073,
    }
    # the breakdown names the scope beside the op
    labels = [name for name, _ in scoped["device_ops"]]
    assert any(name.endswith(" @ffn") for name in labels) and all(len(n) <= 96 for n in labels)


def test_scopes_against_a_plain_sum_over_the_events(scoped):
    """The same numbers without the self-time stack: only the ``while`` nests,
    so every other op's time is its duration, and the ``while``'s own time is
    what its span leaves uncovered."""
    from jax.profiler import ProfileData

    import xplane_meta

    (plane,) = [p for p in ProfileData.from_file(SCOPED).planes if p.name.startswith(rt.DEVICE_PREFIX)]
    names = xplane_meta.op_names(SCOPED)[plane.name]
    by_name = {}
    for (program, name), op_name in names.items():
        assert by_name.setdefault(name, op_name) == op_name  # one program: the name alone decides
    units = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns)) for p in ProfileData.from_file(SCOPED).planes
             if p.name.startswith("/host:CPU") for ln in p.lines for ev in ln.events if ev.name == rt.UNIT_SPAN]
    lo, hi = min(s for s, _ in units), max(e for _, e in units)
    plain = {}
    paths = set()
    for ln in plane.lines:
        if ln.name != rt.OP_LINE:
            continue
        for ev in ln.events:
            s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
            assert lo <= s and e <= hi
            if ev.name.startswith("%while"):
                continue
            scope = xplane_meta.scope_of(by_name.get(ev.name))
            paths.add((scope, "rematted_computation" in (by_name.get(ev.name) or ""), "transpose(jvp(" in (by_name.get(ev.name) or "")))
            plain[scope] = plain.get(scope, 0.0) + (e - s) / 1e9
    # forward ops, what jax.checkpoint computes again and the backward ops all count to their scope
    for scope in ("attn", "ffn"):
        assert {(scope, False, False), (scope, True, True), (scope, False, True)} <= paths
    for scope in ("attn", "ffn", "head_loss", "optimizer"):
        assert scoped["by_scope"][scope] == pytest.approx(plain[scope], rel=1e-9)
    # the whiles' own time is unscoped
    assert scoped["by_scope"]["unscoped"] > plain["unscoped"]
    assert scoped["by_scope"]["unscoped"] - plain["unscoped"] < 1e-6


def test_scope_of_takes_the_innermost_scope_on_the_path():
    from xplane_meta import scope_of

    assert scope_of("jit(tft_grads)/jvp()/while/body/closed_call/attn/bqhd,bkhd->bhqk/dot_general") == "attn"
    assert scope_of("jit(tft_grads)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/ffn/jit(silu)/mul") == "ffn"
    assert scope_of("jit(tft_fused)/transpose(jvp(head_loss))/jit(log_softmax)/sub") == "head_loss"
    assert scope_of("jit(tft_fused)/jvp(embed)/jit(_take)/gather") == "embed"
    assert scope_of("jit(tft_apply)/optimizer/add") == "optimizer"
    assert scope_of("jit(tft_fused)/moe/router/dot_general") == "moe"  # a scope the readers do not know is its parent's
    assert scope_of("jit(tft_fused)/attn/jvp(moe)/dot_general") == "moe"  # the innermost
    for none in ("jit(tft_fused)/transpose(jvp())/while", "jit(tft_fused)/jvp()/squeeze", "", None):
        assert scope_of(none) == "unscoped"
    # a primitive or an inner jit that happens to share a scope's spelling elsewhere in its name does not count
    assert scope_of("jit(tft_fused)/jit(attn_helper)/dot_general") == "unscoped"


def test_a_device_plane_without_any_op_name_is_an_error():
    """A runtime that stops writing ``tf_op`` must not read as a step that is all ``unscoped``."""
    from jax.profiler import ProfileData

    import xplane_meta

    planes = list(ProfileData.from_file(SCOPED).planes)
    names = {name: {} for name in xplane_meta.op_names(SCOPED)}
    with pytest.raises(ValueError, match="names no op_name"):
        rt.reduce_planes(planes, [], op_names=names)
    assert set(rt.reduce_planes(planes, [])["by_scope"]) == {"unscoped"}  # asked for no names: no error


@pytest.mark.parametrize("path", [SCOPED, TRACE])
def test_the_wire_reader_against_the_generated_protobuf_class(path):
    """``xplane_meta.py`` reads the wire format by field number; TensorFlow's generated class,
    where it imports (10 s, 0.8 GB: why the worker does not use it), must read the same."""
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    import xplane_meta

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    want = {}
    for plane in space.planes:
        if not plane.name.startswith(rt.DEVICE_PREFIX):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        ops = want.setdefault(plane.name, {})
        for meta in plane.event_metadata.values():
            stats = {stat_names[s.metadata_id]: s for s in meta.stats}
            if xplane_meta.OP_NAME_STAT not in stats:
                continue
            tf_op = stats[xplane_meta.OP_NAME_STAT]
            text = tf_op.str_value or stat_names.get(tf_op.ref_value, "")
            program = stats.get(xplane_meta.PROGRAM_STAT)
            if text.rsplit(":", 1)[0]:
                ops[(program.uint64_value or program.int64_value if program else 0, meta.name)] = text.rsplit(":", 1)[0]
    assert want and all(want.values())
    assert xplane_meta.op_names(path) == want


def test_a_unit_owns_the_runs_it_launched_whatever_the_devices_clock_says():
    """In the first recorded trace the device's clock runs 1.3 ms ahead of the host's: the first unit's program
    starts 1.26 ms "before" its unit and the third's ends before its unit begins, so by device time the units
    hold 1, 1 and 0 runs' worth of busy time. By when the host enqueued each run, every unit owns its own."""
    units = rt.reduce_file(TRACE, [])["units"]
    assert [round(u["busy_s"] * 1e9) for u in units] == [43451, 43450, 0]
    assert [round(sum(u["by_scope"].values()) * 1e9) for u in units] == [43698, 43451, 43450]


# -- a unit whose ops no program run covers: the third recorded trace (``record_program_spans.py``: three units of
# the ft loop in miniature, ``jit_tft_grads`` and ``jit_tft_apply`` a unit) with events taken out

SPANS = os.path.join(HERE, "recorded_program_spans_v5e.xplane.pb")


def _planes_without(drop):
    """The recorded planes as plain objects, less the events ``drop(plane, line, event)`` names."""
    import types

    from jax.profiler import ProfileData

    data = ProfileData.from_file(SPANS)
    return [
        types.SimpleNamespace(name=p.name, lines=[
            types.SimpleNamespace(name=ln.name, events=[
                types.SimpleNamespace(name=ev.name, start_ns=ev.start_ns, duration_ns=ev.duration_ns, stats=list(ev.stats))
                for ev in ln.events if not drop(p.name, ln.name, ev)
            ]) for ln in p.lines
        ]) for p in data.planes
    ]


@pytest.mark.parametrize("missing", ["module event", "enqueue event"])
def test_what_is_left_of_a_run_launched_before_the_trace_is_no_units(missing):
    """Since PR 27 the host enqueues a step's ``apply`` after the next unit has begun, so the ``apply`` of the step
    before the first traced one is still running when the profiler attaches. The trace then holds its last ops under a
    module event cut short and without an enqueue event (my chip runs, PR 29: 12.8 and 8.6 ms of 25.4, ``optimizer_roofline``
    101.7 and 114.3 %), or without a module event at all (ledger, PR 28: 4.3 ms, 131 %); placed by their start on the
    device's clock they went to the first unit. Here the recorded trace's second ``jit_tft_apply`` loses one or the other."""
    import xplane_meta

    import measure
    import scope_metrics

    names = xplane_meta.op_names(SPANS)
    whole = rt.reduce_planes(_planes_without(lambda *_: False), [], op_names=names)["units"]
    assert [u["unlaunched_s"] for u in whole] == [0.0, 0.0, 0.0]
    second = sorted(
        (ev.start_ns, dict(ev.stats)[rt.RUN_ID]) for p in _planes_without(lambda *_: False) for ln in p.lines
        if ln.name == rt.MODULE_LINE for ev in ln.events if ev.name.startswith("jit_tft_apply")
    )[1]

    def drop(plane, line, ev):
        if missing == "module event":
            return line == rt.MODULE_LINE and ev.start_ns == second[0]
        return ev.name == rt.ENQUEUE_EVENT and dict(ev.stats).get(rt.RUN_ID) == second[1]

    cut = rt.reduce_planes(_planes_without(drop), [], op_names=names)["units"]
    assert "jit_tft_apply" not in cut[1]["programs"] and "jit_tft_apply" in cut[0]["programs"]
    # the run's ops, all scopes of them: its 10.1 us less the gaps between them
    assert 0.75 * whole[1]["programs"]["jit_tft_apply"] < cut[1]["unlaunched_s"] < whole[1]["programs"]["jit_tft_apply"]
    assert cut[0]["unlaunched_s"] == cut[2]["unlaunched_s"] == 0.0
    assert "optimizer" not in cut[1]["by_scope"] and cut[0]["by_scope"] == whole[0]["by_scope"]
    assert sum(cut[1]["by_scope"].values()) + cut[1]["unlaunched_s"] == pytest.approx(sum(whole[1]["by_scope"].values()))
    # the unit's scopes are its other run's ops and no more (placed by their start they came to 112 % of it) ...
    assert 0.95 < sum(cut[1]["by_scope"].values()) / sum(cut[1]["programs"].values()) <= 1.0
    # ... and the optimizer's reader takes the units that ran one: the first unit's, not half of it
    bare = lambda units: [{k: v for k, v in u.items() if k != "programs"} for u in units]
    run = lambda units: measure.Run({}, {}, {"steps_per_unit": 1}, None, [{"group": 0, "trace": {"units": bare(units)}}])
    assert scope_metrics.device_seconds(run(cut), "optimizer") == whole[0]["by_scope"]["optimizer"]
    assert scope_metrics.device_seconds(run(whole), "optimizer") == pytest.approx(
        (whole[0]["by_scope"]["optimizer"] + whole[1]["by_scope"]["optimizer"]) / 2)


def test_without_any_enqueue_event_no_run_is_placed():
    """A trace that names no launch (another runtime's layout) gives the readers nothing to read: better than runs
    placed by the device's clock, which runs 1.3 ms ahead of the host's here."""
    import xplane_meta

    bare = rt.reduce_planes(_planes_without(lambda p, ln, ev: ev.name == rt.ENQUEUE_EVENT), [],
                            op_names=xplane_meta.op_names(SPANS))
    assert all(u["programs"] == {} and u["by_scope"] == {} for u in bare["units"])
    assert sum(u["unlaunched_s"] for u in bare["units"]) > 0
    assert bare["busy_s"] > 0 and bare["by_scope"]  # the window's totals do not depend on who owns what
