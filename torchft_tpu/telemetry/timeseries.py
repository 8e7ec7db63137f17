"""Time-series plane: replica-side series publication + range-query client.

The lighthouse keeps a fixed-retention ring of samples per
``(replica, series)`` (``native/tsdb.h``), keyed by the clock-sync-free
``(epoch, step)`` coordinates and fed by the SAME quorum-piggyback
telemetry that already carries the summary/anatomy digests — zero extra
control-plane round trips. This module is both ends of that pipe:

* :func:`build_series` — the replica side. Builds the flat
  ``{name: float}`` sample map the Manager attaches to its telemetry
  payload each step: the last step row's wall/local/per-phase seconds
  (``telemetry.anatomy.StepLedger.last_row`` — raw per-step values, not
  percentiles, because percentile smoothing would hide a level shift
  from whoever reads the ring), the rolling local p50, lathist-derived
  native p50/p99s, and the SLO/stuck/divergence
  flags as 0/1 series. The lighthouse stays schema-blind: names are
  opaque strings, so this vocabulary can evolve without touching C++.

* :func:`poll_timeseries` — the fleet side. One ``GET /timeseries.json``
  range query (``since`` step cursor, ``max_points`` stride
  downsampling, replica/series substring filters) against the
  lighthouse. Its readers are operators (the route itself) and the
  diagnosis bundle's tsdb window (:mod:`torchft_tpu.telemetry.diagnosis`).

Series vocabulary published by :func:`build_series` (all seconds unless
flagged):

``wall_s`` / ``local_s``
    the last step's wall clock and LOCAL (peer-wait-excluded) time;
``local_p50_s``
    the rolling local p50 (same scalar the straggler detector reads);
``phase.<name>``
    the last step's per-phase seconds for every active anatomy phase;
``lat.<op>.p50_s`` / ``lat.<op>.p99_s``
    native latency quantiles (dp.hop / dp.stripe / rpc.serve /
    quorum.fanout) from this process's lathist snapshot;
``flag.slo_breach`` / ``flag.stuck`` / ``flag.divergence``
    detector latches as 0/1 series, so "when did it latch" is a range
    query instead of archaeology.

Knob registry (documented in docs/observability.md "Time series",
enforced both directions by the ``obs-env-drift`` analysis rule):

====================================  =====================================
``TORCHFT_TSDB_SERIES``               ``0`` disables the per-step series
                                      piggyback (default on)
``TORCHFT_TSDB_RETAIN``               lighthouse ring length per
                                      (replica, series), samples
                                      (default 512); also this client's
                                      assumption about how much history a
                                      full-range query can return
``TORCHFT_TSDB_MAX_SERIES``           per-replica series fan-out cap, both
                                      sides: the builder trims its map to
                                      this size and the lighthouse refuses
                                      (loudly: ``tsdb_dropped_series``)
                                      anything past it (default 64)
====================================  =====================================
"""

from __future__ import annotations

import json
import os
import urllib.request
from typing import Any, Dict, List, Optional

__all__ = [
    "DEFAULT_RETAIN",
    "DEFAULT_MAX_SERIES",
    "series_enabled",
    "build_series",
    "poll_timeseries",
]

DEFAULT_RETAIN = 512
DEFAULT_MAX_SERIES = 64


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def retain() -> int:
    """The lighthouse-side ring length this deployment runs with (the
    native store reads the same env)."""
    return _env_int("TORCHFT_TSDB_RETAIN", DEFAULT_RETAIN)


def max_series() -> int:
    return _env_int("TORCHFT_TSDB_MAX_SERIES", DEFAULT_MAX_SERIES)


def series_enabled() -> bool:
    return os.environ.get("TORCHFT_TSDB_SERIES", "1") != "0"


def build_series(
    slo_breach: bool = False,
    stuck: bool = False,
    divergence: bool = False,
) -> Optional[Dict[str, float]]:
    """The replica's sample map for this step's piggyback (see module
    docstring for the vocabulary); None when disabled or before the
    first step row. Never raises — observability must not fail quorum."""
    if not series_enabled():
        return None
    try:
        from torchft_tpu import telemetry
        from torchft_tpu.telemetry.anatomy import lathist_quantile

        row = telemetry.LEDGER.last_row()
        if row is None:
            return None
        out: Dict[str, float] = {
            "wall_s": float(row["wall_s"]),
            "local_s": float(row["local_s"]),
        }
        p50 = telemetry.LEDGER.local_p50()
        if p50 is not None:
            out["local_p50_s"] = float(p50)
        for phase, seconds in row["phases"].items():
            out[f"phase.{phase}"] = float(seconds)
        try:
            from torchft_tpu.telemetry.native import native_latency_snapshot

            native = native_latency_snapshot()
            for op, hist in (native or {}).items():
                if int(hist.get("count", 0)):
                    out[f"lat.{op}.p50_s"] = float(
                        lathist_quantile(hist, 0.5)
                    )
                    out[f"lat.{op}.p99_s"] = float(
                        lathist_quantile(hist, 0.99)
                    )
        except Exception:  # noqa: BLE001 — native plane optional
            pass
        out["flag.slo_breach"] = 1.0 if slo_breach else 0.0
        out["flag.stuck"] = 1.0 if stuck else 0.0
        out["flag.divergence"] = 1.0 if divergence else 0.0
        cap = max_series()
        if len(out) > cap:
            # deterministic PRIORITY trim — the lighthouse would refuse
            # the overflow anyway; trimming here controls WHICH series
            # survive. Ordered by what a reader of the ring needs
            # first, not alphabetically: wall/local and the phase
            # decomposition are the step's account and must outlive
            # diagnostics like lat.* quantiles and the 0/1 flags
            # (a lexicographic trim would cut wall_s FIRST and keep
            # flag.* — exactly backwards).
            def rank(name: str) -> int:
                if name in ("wall_s", "local_s", "local_p50_s"):
                    return 0
                if name.startswith("phase."):
                    return 1
                if name.startswith("flag."):
                    return 2
                return 3  # lat.* and anything future

            out = dict(
                sorted(out.items(), key=lambda kv: (rank(kv[0]), kv[0]))
                [:cap]
            )
        return out
    except Exception:  # noqa: BLE001
        return None


def _base_url(addr: str) -> str:
    if "://" not in addr:
        addr = "http://" + addr
    return addr.rstrip("/")


def poll_timeseries(
    addr: str,
    replica: str = "",
    series: str = "",
    since: Optional[int] = None,
    max_points: Optional[int] = None,
    timeout: float = 3.0,
) -> Optional[Dict[str, Any]]:
    """One range query against the lighthouse's ``GET /timeseries.json``.
    Filters are substring matches; ``since`` is an exclusive step cursor
    (the reply's ``cursor.max_step`` is the next value); ``max_points``
    stride-downsamples each series (the newest sample always survives).
    Returns the parsed reply or None when unreachable — observability
    degrades, never raises."""
    params: List[str] = []
    if replica:
        params.append(f"replica={replica}")
    if series:
        params.append(f"series={series}")
    if since is not None:
        params.append(f"since={int(since)}")
    if max_points is not None:
        params.append(f"max_points={int(max_points)}")
    url = f"{_base_url(addr)}/timeseries.json"
    if params:
        url += "?" + "&".join(params)
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return json.loads(resp.read().decode())
    except Exception:  # noqa: BLE001
        return None
