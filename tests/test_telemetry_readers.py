"""Every name the program writes that something reads, by name.

41 of the per-layer metrics of ``BENCHMARK.json`` have source ``program_span``
or ``program_counter``: they are computed from spans, stats and ledger phases
that the PROGRAM writes. A deletion on the program's side that drops one turns
the metric ``null`` on the ledger without failing anything here — unless the
name is a row of ``READERS``. One row, one case: the name is written by the
fault-tolerant step of ``conftest.traced`` (three steps of a one-group job
under a profiler session; the ledger's rows from three more with the quorum
held), with a value of the expected type. A row's comment
names the reader. What a telemetry name needs to stay in the program is a row
here, a safety property with a test of its own, or a documented operator
route (docs/observability.md, "What earns a place here").

Not rows, because another test already fails by the name:

* ``heal_end``'s ``duration_s`` and ``heal_stats`` (``bootstrap_heal_s``,
  ``heal_meta_s``, ``heal_recv_s``, ``heal_fetch_streams``; read through
  ``benchmark/heal_stats.py``): a one-group job heals nobody.
  ``tests/test_manager.py::test_heal_uses_multi_source_with_cohort`` holds the
  event to both fields, ``tests/test_heal_plane.py``
  (``test_two_sources_bit_identical``, ``test_one_source_several_ranges_in_flight``)
  the transport to ``stages.meta_s`` / ``recv_s`` and ``streams``.
* where a span lies (thread, nesting, order) and what a bucket's stats add up
  to: ``tests/test_tft_spans.py``; the ring account's arithmetic:
  ``tests/test_ring_account.py``; the build account's:
  ``tests/test_build_account.py``; ``buckets_from_source`` above 0:
  ``tests/test_ddp_source.py``.
* ``hbm_peak_gb`` and ``compile_s_in_setup`` read JAX (``memory_stats``, its
  compile log), not the program.
"""

import numbers

import pytest

NUMBER = numbers.Real


def row(kind, name, field, kind_of_value, reader):
    return pytest.param(
        kind, name, field, kind_of_value, reader,
        id=f"{kind}:{name}" + (f".{field}" if field else "") + f"->{reader}",
    )


# kind "phase": a phase of the rows of ``telemetry.LEDGER.dump()["rows"]``
#      "row":   a key of such a row
#      "span":  a ``tft.<name>`` event on some host thread of the trace
#      "stat":  a stat of every such event
#      "ring":  a key of the Tracer ring's ``exchange`` spans, or of their attrs
READERS = [
    # benchmark/measure.py ledger_phase_median, through loops/ft.py's ledger_rows
    row("phase", "quorum_wait", None, NUMBER, "quorum_commit_s"),
    row("phase", "commit_barrier", None, NUMBER, "quorum_commit_s"),
    row("phase", "wire", None, NUMBER, "wire_s"),
    row("row", "step", None, int, "quorum_commit_s"),  # joins a row to a window's unit
    # benchmark/program_spans.py Trace.seconds / .idle_seconds of one name
    row("span", "exchange.d2h_wait", None, None, "exchange_d2h_wait_s"),
    row("span", "exchange.pack", None, None, "exchange_pack_s"),
    row("span", "exchange.ring", None, None, "exchange_ring_s"),
    row("span", "exchange.h2d", None, None, "exchange_h2d_s"),
    row("span", "commit.prepare", None, None, "commit_prepare_s"),
    row("span", "loss_sync", None, None, "loss_sync_s"),
    # Trace.self_seconds: a span less what is nested in it on the main thread,
    # so the children are read as much as the parent
    row("span", "exchange", None, None, "exchange_unattributed_s"),
    row("span", "exchange.d2h_issue", None, None, "exchange_unattributed_s"),
    row("span", "exchange.plan", None, None, "exchange_unattributed_s"),
    row("span", "exchange.submit", None, None, "exchange_unattributed_s"),
    row("span", "exchange.tail_wait", None, None, "exchange_unattributed_s"),
    row("span", "exchange.reassemble", None, None, "exchange_unattributed_s"),
    row("span", "step", None, None, "step_unattributed_s"),
    row("span", "quorum.start", None, None, "step_unattributed_s"),
    row("span", "shard_batch", None, None, "step_unattributed_s"),
    row("span", "grads", None, None, "step_unattributed_s"),
    row("span", "commit", None, None, "step_unattributed_s"),
    row("span", "apply", None, None, "step_unattributed_s"),
    # the zero-length carriers of the stats below
    row("span", "exchange.counters", None, None, "exchange_user_cpu_s"),
    row("span", "exchange.ring.account", None, None, "ring_unattributed_s"),
    row("span", "build.counters", None, None, "build_trace_s_in_setup"),
    # benchmark/program_spans.py exchange_counter_median
    row("stat", "exchange.counters", "utime_s", NUMBER, "exchange_user_cpu_s"),
    row("stat", "exchange.counters", "stime_s", NUMBER, "exchange_sys_cpu_s"),
    row("stat", "exchange.counters", "buckets_reused", NUMBER, "exchange_buckets_reused"),
    row("stat", "exchange.counters", "d2h_pages_kept", NUMBER, "exchange_d2h_pages_kept"),
    row("stat", "exchange.counters", "buckets_avg_in_ring", NUMBER, "exchange_buckets_avg_in_ring"),
    row("stat", "exchange.counters", "buckets_from_source", NUMBER, "exchange_buckets_from_source"),
    row("stat", "exchange.counters", "pieces", NUMBER, "exchange_pieces"),
    # benchmark/exchange_account.py counter / rate
    row("stat", "exchange.counters", "ring_wait_s", NUMBER, "ring_neighbour_wait_s+ring_wait_imbalance_s"),
    row("stat", "exchange.counters", "ring_pull_s", NUMBER, "ring_pull_s+ring_pull_gbps"),
    row("stat", "exchange.counters", "ring_pull_bytes", NUMBER, "ring_pull_gbps+exchange_copied_gb"),
    row("stat", "exchange.counters", "ring_reduce_s", NUMBER, "ring_reduce_s"),
    row("stat", "exchange.counters", "ring_reduce_bytes", NUMBER, "exchange_copied_gb"),
    row("stat", "exchange.counters", "pack_s", NUMBER, "exchange_pack_slowest_gbps"),
    row("stat", "exchange.counters", "pack_bytes", NUMBER, "exchange_pack_slowest_gbps+exchange_copied_gb"),
    row("stat", "exchange.counters", "pack_aliased_bytes", NUMBER, "exchange_pack_aliased_gb"),
    row("stat", "exchange.counters", "bytes_d2h", NUMBER, "exchange_copied_gb"),
    row("stat", "exchange.counters", "h2d_bytes", NUMBER, "exchange_copied_gb"),
    # benchmark/exchange_account.py ring_unattributed: ACCOUNTED
    row("stat", "exchange.ring.account", "desc_wait_s", NUMBER, "ring_unattributed_s"),
    row("stat", "exchange.ring.account", "ack_wait_s", NUMBER, "ring_unattributed_s"),
    row("stat", "exchange.ring.account", "pull_s", NUMBER, "ring_unattributed_s"),
    row("stat", "exchange.ring.account", "reduce_s", NUMBER, "ring_unattributed_s"),
    row("stat", "exchange.ring.account", "pump_s", NUMBER, "ring_unattributed_s"),
    row("stat", "exchange.ring.account", "codec_s", NUMBER, "ring_unattributed_s"),
    # benchmark/build_account.py slowest_group
    row("stat", "build.counters", "trace_s", NUMBER, "build_trace_s_in_setup"),
    row("stat", "build.counters", "lower_s", NUMBER, "build_lower_s_in_setup"),
    row("stat", "build.counters", "load_s", NUMBER, "build_load_s_in_setup"),
    row("stat", "build.counters", "compile_s", NUMBER, "build_compile_s_in_setup"),
    row("stat", "build.counters", "cache_misses", NUMBER, "build_cache_misses_in_setup"),
    row("stat", "build.counters", "step_program_s", NUMBER, "build_step_program_s_in_setup"),
    row("stat", "build.counters", "first_call_s", NUMBER, "step_program_first_call_s"),
    # benchmark/loops/ft.py close(): a group's exchange_spans, on its line of
    # the output traced or not
    row("ring", "exchange", "dur_s", NUMBER, "exchange_spans"),
    row("ring", "exchange", "attrs", dict, "exchange_spans"),
]


def written(traced, kind, name, field):
    """The values the program wrote under a row's name, one a record."""
    if kind == "phase":
        # the reader takes a row without the phase as 0.0: the first step's
        # row closes before some are booked
        return [r["phases"][name] for r in traced.ledger_rows if name in r["phases"]]
    if kind == "row":
        return [r.get(name) for r in traced.ledger_rows]
    if kind in ("span", "stat"):
        events = [ev for line in traced.lines for ev in line.get(name, ())]
        return [True if kind == "span" else stats.get(field) for _, _, stats in events]
    return [s.get(field) for s in traced.ring_spans if s["name"] == name]


@pytest.mark.parametrize("kind, name, field, kind_of_value, reader", READERS)
def test_the_program_writes_what_is_read(traced, kind, name, field, kind_of_value, reader):
    values = written(traced, kind, name, field)
    assert values, f"nothing named {name!r} was written: {reader} would read null"
    for value in values:
        assert value is not None, f"{name}.{field} is missing from a record: {reader}"
        if kind_of_value is not None:
            assert isinstance(value, kind_of_value) and not isinstance(value, bool), (name, field, value)

