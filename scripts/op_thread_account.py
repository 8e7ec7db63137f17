#!/usr/bin/env python3
"""The collectives op thread's account of a traced step, from a ``.xplane.pb``.

The host-path exchange ends by waiting for ONE thread (``CollectivesTcp``'s
op thread): it runs each bucket's ring (``tft.exchange.ring``), then the
callbacks hung on the op's future — ``ddp``'s ``scatter``
(``tft.exchange.h2d``) and, where the ring could not take the average, the
fallback division (``tft.exchange.average``). This prints, per traced step
(one ``tft.exchange`` span on the main thread), what that thread did between
its first ring's start and its last callback's end: seconds in each span,
and the rest — time with a ring already queued that no span names
(PERF.md §5, "the op thread's account"). A gap in which the next ring had
not been submitted yet is the main thread's (starved), not the op thread's.
Beside each ring's ``queued_s`` and ``divisor`` it prints the ring's own
account of its hops (``tft.exchange.ring.account``: waiting for a neighbour,
pulling, reducing — ``collectives.RING_ACCOUNT``), and per step the pack's
``dst_ahead_b`` by bucket and, by name, the seconds of the other threads'
``tft.exchange.*`` spans between the exchange's start and its first ring's
(``before_first_ring_s``: which piece a late first ring waited for).

Beside each ring's start it prints when its piece was ready — its bucket's
landing wait returned on the main thread (``tft.exchange.d2h_wait``'s end) —
and whether the chip still computed the gradient tree then
(``tft.exchange.submit``'s ``under_grads``): a ring that starts as its piece
lands waited for the chip (``under_grads`` 1) or for the landing copy (0),
one that starts later waited for the ring before it (``rings_at``).

    python scripts/op_thread_account.py benchmark_runs/<cell>/trace.*   # after a --trace 1 run
    python scripts/op_thread_account.py benchmark_runs/<cell>/result.0.json   # any run, traced or not

One JSON line a trace on stdout; needs ``jax.profiler.ProfileData`` only
(no backend is initialised). A worker's ``result.<g>.json`` says the same of
an UNTRACED run, from the ``exchange`` span's own attributes
(``bucket_landed_s``, ``bucket_ring_end_s``, ``bucket_under_grads``; a traced
run blocks on the whole tree before it exchanges, so only an untraced one
shows what lies under the backward): per exchange and bucket, when the piece
landed, when its ring ended, and what the op thread waited for before it —
``ring`` (the bucket before was still on it), ``chip`` or ``landing``.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys

PREFIX = "tft.exchange"
RING, H2D, AVERAGE = f"{PREFIX}.ring", f"{PREFIX}.h2d", f"{PREFIX}.average"
ACCOUNT, PACK = f"{PREFIX}.ring.account", f"{PREFIX}.pack"
LANDED, SUBMIT = f"{PREFIX}.d2h_wait", f"{PREFIX}.submit"


def _xplane(path: str) -> str:
    if os.path.isdir(path):
        hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not hits:
            raise SystemExit(f"no .xplane.pb under {path}")
        return hits[-1]
    return path


def account(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_xplane(path))
    exchanges, by_line, counters, accounts, packs, pieces = [], {}, [], [], [], []
    landed, submits = [], []  # (end, stats) of each bucket's landing wait; (start, stats) of its submit
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if not ev.name.startswith(PREFIX):
                    continue
                s = float(ev.start_ns) / 1e9
                e = s + float(ev.duration_ns) / 1e9
                if ev.name == PREFIX:
                    exchanges.append((s, e))
                elif ev.name == f"{PREFIX}.counters":
                    counters.append((s, dict(ev.stats)))
                elif ev.name == ACCOUNT:
                    accounts.append((s, dict(ev.stats)))
                elif ev.name == PACK:
                    packs.append((s, dict(ev.stats)))
                elif ev.name == LANDED:
                    landed.append((e, dict(ev.stats)))
                elif ev.name == SUBMIT:
                    submits.append((s, dict(ev.stats)))
                if ev.name not in (PREFIX, RING, H2D, AVERAGE, ACCOUNT, f"{PREFIX}.counters"):
                    pieces.append((s, e, ev.name[len(PREFIX) + 1:]))
                elif ev.name in (RING, H2D, AVERAGE):
                    by_line.setdefault((plane.name, li), []).append(
                        (s, e, ev.name, dict(ev.stats))
                    )
    # the op thread's line is the one that holds the rings
    op = [evs for evs in by_line.values() if any(n == RING for _, _, n, _ in evs)]
    steps = []
    for lo, hi in sorted(exchanges):
        evs = sorted(ev for line in op for ev in line if lo <= ev[0] < hi)
        rings = [ev for ev in evs if ev[2] == RING]
        if not rings:
            continue
        named = {RING: 0.0, H2D: 0.0, AVERAGE: 0.0}
        for s, e, n, _ in evs:
            named[n] += e - s
        # between two rings: what no span covers, split at the next ring's
        # submission (its start less queued_s): before it the thread had
        # nothing to run
        between = starved = 0.0
        for a, b in zip(rings, rings[1:]):
            submitted = b[0] - float(b[3].get("queued_s", 0.0))
            x = a[1]
            for s, e, n, _ in evs + [(b[0], b[0], "", {})]:
                if n == RING or not a[1] <= s <= b[0]:
                    continue
                if s > x:
                    cut = min(max(submitted, x), s)
                    starved += cut - x
                    between += s - cut
                x = max(x, e)
        end = max(e for _, e, _, _ in evs)
        after = (end - rings[-1][1]) - sum(
            e - s for s, e, n, _ in evs if n != RING and s >= rings[-1][1]
        )
        step = {
            "rings": len(rings),
            "ring_s": named[RING],
            "h2d_s": named[H2D],
            "average_s": named[AVERAGE],
            "average_events": sum(1 for ev in evs if ev[2] == AVERAGE),
            "between_rings_unnamed_s": between,
            "after_last_ring_unnamed_s": after,
            "starved_s": starved,
            "op_thread_span_s": end - rings[0][0],
            "first_ring_after_exchange_start_s": rings[0][0] - lo,
            "exchange_s": hi - lo,
            "last_ring_queued_s": float(rings[-1][3].get("queued_s", 0.0)),
            "ring_divisors": sorted({r[3].get("divisor") for r in rings}, key=str),
        }
        # the n-th account of a step is its n-th ring's (one op thread)
        mine = sorted(((s, a) for s, a in accounts if lo <= s <= hi), key=lambda sa: sa[0])
        if len(mine) == len(rings):
            step["ring_accounts"] = [
                {"ring_s": e - s, "queued_s": st.get("queued_s"), "divisor": st.get("divisor"),
                 **{k: v for k, v in a.items() if k != "bytes"}}
                for (s, e, _, st), (_, a) in zip(rings, mine)
            ]
        # the n-th ring of a step is its n-th bucket's: when that piece was
        # ready, beside when its ring started
        ready = {st.get("bucket"): t - lo for t, st in landed if lo <= t <= hi}
        under = {st.get("bucket"): st.get("under_grads") for t, st in submits if lo <= t <= hi}
        step["rings_at"] = [
            {"bucket": k, "piece_ready_s": ready.get(k), "under_grads": under.get(k),
             "ring_start_s": r[0] - lo, "ring_end_s": r[1] - lo}
            for k, r in enumerate(rings)
        ]
        before = {}
        for s, e, name in pieces:
            if lo <= s < rings[0][0]:
                before[name] = before.get(name, 0.0) + min(e, rings[0][0]) - s
        step["before_first_ring_s"] = before
        step["first_ring_queued_s"] = float(rings[0][3].get("queued_s", 0.0))
        ahead = [p.get("dst_ahead_b") for s, p in sorted(packs, key=lambda sp: sp[0]) if lo <= s < hi]
        if any(a is not None for a in ahead):
            step["pack_dst_ahead_b"] = ahead
        for s, stats in counters:
            if lo <= s <= hi + 1e-3:
                for k in ("buckets", "buckets_reused", "buckets_avg_in_ring", "buckets_from_source",
                          "pack_s", "pack_bytes", "pack_aliased_bytes", "tail_wait_s",
                          "ring_wait_s", "ring_pull_s", "ring_reduce_s"):
                    if k in stats:
                        step[k] = stats[k]
        steps.append(step)
    out = {"trace": path, "steps": steps}
    if steps:
        out["median"] = {
            k: statistics.median(st[k] for st in steps)
            for k, v in steps[0].items()
            if isinstance(v, float)
        }
    return out


def untraced(path: str) -> dict:
    """The same question of a worker's ``result.<g>.json``: per exchange of the
    Tracer's ring and per bucket, when the piece landed, when its ring ended,
    and what the op thread waited for before that ring."""
    with open(path) as f:
        spans = json.load(f).get("exchange_spans") or []
    steps = []
    for span in spans:
        if "bucket_landed_s" not in span:
            continue
        landed = [float(t) for t in span["bucket_landed_s"].split(",")]
        ended = [float(t) for t in span["bucket_ring_end_s"].split(",")]
        under = [int(u) for u in span["bucket_under_grads"].split(",")]
        buckets, free = [], 0.0  # when the op thread had its last ring behind it
        for k, (at, end, u) in enumerate(zip(landed, ended, under)):
            waited = "ring" if free > at else "chip" if u else "landing"
            buckets.append({"bucket": k, "piece_ready_s": at, "ring_end_s": end,
                            "op_thread_idle_before_s": max(at - free, 0.0), "waited_for": waited})
            free = end
        steps.append({
            "step": span.get("step"), "exchange_s": span["dur_s"], "pieces": span.get("pieces"),
            "bytes_under_grads": span.get("bytes_under_grads"), "buckets": buckets,
            "waited_for_chip_s": sum(b["op_thread_idle_before_s"] for b in buckets if b["waited_for"] == "chip"),
            "waited_for_landing_s": sum(b["op_thread_idle_before_s"] for b in buckets if b["waited_for"] == "landing"),
        })
    out = {"result": path, "steps": steps}
    if steps:
        out["median"] = {
            k: statistics.median(st[k] for st in steps)
            for k in ("exchange_s", "bytes_under_grads", "waited_for_chip_s", "waited_for_landing_s")
        }
        out["median"]["piece_ready_s"] = [statistics.median(col) for col in zip(*([b["piece_ready_s"] for b in st["buckets"]] for st in steps))]
        out["median"]["ring_end_s"] = [statistics.median(col) for col in zip(*([b["ring_end_s"] for b in st["buckets"]] for st in steps))]
    return out


def main(argv) -> int:
    if not argv:
        raise SystemExit(__doc__)
    for path in argv:
        print(json.dumps(untraced(path) if path.endswith(".json") else account(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
