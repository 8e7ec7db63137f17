#!/usr/bin/env bash
# Pre-merge gate — the checklist that used to live only as prose in
# docs/static_analysis.md, as one runnable script (ISSUE 11, extended by
# ISSUE 15 and ISSUE 20):
#
#   1. the static-analysis gate  (python -m torchft_tpu.analysis —
#      concurrency lint, wire/doc drift, and the clang-free native
#      concurrency lint; incrementally cached under .analysis_cache/)
#   2. the native strict-warning build  (make -C native warn, -Werror);
#      when clang-tidy is on PATH the full `make -C native tidy` gate
#      runs too instead of being silently skipped
#   3. the quick faultmatrix subset  (runner --quick) — every scenario
#      now also replays spec-conformance-clean or fails
#   4. the protocol verification gate (ISSUE 15/20): bounded model check
#      of the quorum/commit spec AND the HA lighthouse tier (crash at
#      every transition point, POR+symmetry reductions) + a conformance
#      replay of the quick matrix's trails
#
# Exit 0 = every gate clean. Each gate runs even if an earlier one
# failed, so one invocation reports the full damage; the exit code is
# the OR of the gates. Tier-1 pytest is NOT included here — it has its
# own driver and a ~15 min budget; this script is the fast (<10 min)
# "can I even propose this diff" check.
#
# Usage:
#   scripts/premerge.sh              # all four gates
#   scripts/premerge.sh --no-matrix  # skip the faultmatrix (seconds-fast;
#                                    # gate 4 then skips the replay leg)
#   scripts/premerge.sh --json       # append a machine-readable per-gate
#                                    # summary (name/status/seconds) as the
#                                    # final stdout line — skips (e.g. the
#                                    # clang-tidy exit-3 skip) are VISIBLE
#                                    # records, never silent
#
# The gate-name ids recorded by --json are drift-checked against the
# docs/static_analysis.md "Pre-merge gates" table by
# `python -m torchft_tpu.analysis` (docdrift: premerge-gate-drift).
set -u -o pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO"

RUN_MATRIX=1
JSON_OUT=0
for arg in "$@"; do
  case "$arg" in
    --no-matrix) RUN_MATRIX=0 ;;
    --json) JSON_OUT=1 ;;
    *) echo "unknown arg: $arg (known: --no-matrix --json)" >&2
       exit 2 ;;
  esac
done

rc=0
GATE_RECORDS=()
fail() { echo "premerge: GATE FAILED: $1" >&2; rc=1; }
# record_gate <name> <passed|failed|skipped> <seconds> — one record per
# gate id; the docdrift premerge-gate-drift rule greps these call sites
record_gate() {
  GATE_RECORDS+=("{\"name\":\"$1\",\"status\":\"$2\",\"seconds\":$3}")
}

echo "=== [1/4] static-analysis gate (python -m torchft_tpu.analysis) ==="
t0=$SECONDS
if JAX_PLATFORMS=cpu python -m torchft_tpu.analysis; then
  record_gate "analysis" passed $((SECONDS - t0))
else
  fail "analysis"
  record_gate "analysis" failed $((SECONDS - t0))
fi

echo "=== [2/4] native strict-warning build (make -C native warn) ==="
t0=$SECONDS
if make -C native warn; then
  record_gate "native-warn" passed $((SECONDS - t0))
else
  fail "native warn"
  record_gate "native-warn" failed $((SECONDS - t0))
fi
# the real clang-tidy gate, when the toolchain is present: exit-3
# (clang-tidy missing) stays a skip with a message AND a skipped record
# in the --json summary, but a container that HAS clang-tidy runs the
# full baseline-diffed gate — no more silently weaker checking on
# better-equipped boxes
if command -v clang-tidy >/dev/null 2>&1; then
  echo "--- clang-tidy present: running make -C native tidy"
  t0=$SECONDS
  if make -C native tidy; then
    record_gate "native-tidy" passed $((SECONDS - t0))
  else
    fail "native tidy"
    record_gate "native-tidy" failed $((SECONDS - t0))
  fi
else
  echo "--- clang-tidy not on PATH: tidy gate skipped (make warn ran)"
  record_gate "native-tidy" skipped 0
fi

MATRIX_DIR="${TMPDIR:-/tmp}/premerge_faultmatrix"
if [ "$RUN_MATRIX" = 1 ]; then
  echo "=== [3/4] quick faultmatrix subset (runner --quick) ==="
  t0=$SECONDS
  if JAX_PLATFORMS=cpu python -m torchft_tpu.faultinject.runner --quick \
      --outdir "$MATRIX_DIR"; then
    record_gate "faultmatrix-quick" passed $((SECONDS - t0))
  else
    fail "faultmatrix --quick"
    record_gate "faultmatrix-quick" failed $((SECONDS - t0))
  fi
else
  echo "=== [3/4] faultmatrix skipped (--no-matrix) ==="
  record_gate "faultmatrix-quick" skipped 0
fi

echo "=== [4/4] protocol verification (model check + conformance replay) ==="
PROTO_ARGS=()
if [ "$RUN_MATRIX" = 1 ] && [ -d "$MATRIX_DIR" ]; then
  PROTO_ARGS+=(--conformance "$MATRIX_DIR")
fi
t0=$SECONDS
if JAX_PLATFORMS=cpu python -m torchft_tpu.analysis.protocol \
    ${PROTO_ARGS[@]+"${PROTO_ARGS[@]}"}; then
  record_gate "protocol" passed $((SECONDS - t0))
else
  fail "protocol verification"
  record_gate "protocol" failed $((SECONDS - t0))
fi

if [ "$rc" = 0 ]; then
  echo "premerge: all gates clean"
fi
if [ "$JSON_OUT" = 1 ]; then
  ok=$([ "$rc" = 0 ] && echo true || echo false)
  gates=$(IFS=,; echo "${GATE_RECORDS[*]}")
  echo "{\"ok\":${ok},\"gates\":[${gates}]}"
fi
exit "$rc"
