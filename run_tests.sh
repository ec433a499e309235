#!/usr/bin/env bash
# One-shot test harness (the reference's test/run_tests.sh analogue, which
# booted a 2-worker local Spark Standalone cluster around unittest discover).
#
# Without pyspark: the suite runs against the bundled local multi-process
# backend (the Spark stand-in; same executor-process semantics).
# With pyspark installed: additionally boots a local-cluster master so the
# integration tests can target real Spark executors.
#
# Usage: ./run_tests.sh [--quick] [--chaos] [--perf-smoke] [--trace-smoke]
#                       [--analyze] [--native-sanitize] [--multichip]
#                       [extra pytest args]
#   --quick       run the quick tier only (pytest -m 'not slow')
#   --chaos       run the quick tier under a fixed low-probability ChaosPlan and
#                 assert that at least one fault was actually injected
#   --trace-smoke run the tracing-plane end-to-end leg: a 1-executor train
#                 with TOS_TRACE_DIR set (flight shards from driver, executor,
#                 and jax child) under a benign one-shot chaos fault, then
#                 merge the shards and validate the Chrome trace schema
#                 (required keys, monotone ts per track, matched B/E pairs)
#                 and that the fault force-dumped a flight ring
#   --multichip   run only the mesh legs: hybrid-mesh placement, the dp×tp /
#                 fsdp / ring numeric-parity gates on forced cpu devices, and
#                 the 2-rank dp×tp gloo world (the jitted step across two
#                 processes)
#   --perf-smoke  run only the perf_smoke marker leg: structural pipelining
#                 assertions (sleep-staged IO/parse overlap — proves the
#                 read-ahead actually overlaps, no absolute-throughput flake)
#                 plus the async-checkpoint overlap leg (a ckpt.write_slow
#                 stall holds the background writer while the training loop
#                 keeps stepping — tests/test_ckpt_chaos.py::TestOverlap)
#   --analyze     write the full tosa static-analysis report to
#                 tosa-report.json and tosa-report.sarif (SARIF 2.1.0 for
#                 code-scanning upload), print the JSON, and exit
#   --native-sanitize  rebuild native/tfrecord_io.cc with ASan+UBSan and run
#                 the native IO / streaming-chunk / JPEG-decode tests against
#                 it — including the header-fuzz loop (truncated and overlong
#                 JPEG streams, lying segment lengths) over the in-tree scalar
#                 decoder, which the sanitize build selects by not defining
#                 TFR_USE_LIBJPEG (skips cleanly when no g++ toolchain is
#                 present)
set -euo pipefail
cd "$(dirname "$0")"

CHAOS=0
PERF_SMOKE=0
TRACE_SMOKE=0
NATIVE_SANITIZE=0
MULTICHIP=0
EXTRA=()
for arg in "$@"; do
  if [[ "$arg" == "--quick" ]]; then
    EXTRA+=(-m "not slow")
  elif [[ "$arg" == "--chaos" ]]; then
    CHAOS=1
    EXTRA+=(-m "not slow")
  elif [[ "$arg" == "--perf-smoke" ]]; then
    PERF_SMOKE=1
  elif [[ "$arg" == "--trace-smoke" ]]; then
    TRACE_SMOKE=1
  elif [[ "$arg" == "--analyze" ]]; then
    exec python -m tosa --json --out tosa-report.json --sarif-out tosa-report.sarif
  elif [[ "$arg" == "--native-sanitize" ]]; then
    NATIVE_SANITIZE=1
  elif [[ "$arg" == "--multichip" ]]; then
    MULTICHIP=1
  else
    EXTRA+=("$arg")
  fi
done

# static-analysis gate, two-phase (per-file walks + project-wide index,
# phase 1 parallel over min(4, cpu) workers): jit purity/host-sync, retry
# & lock discipline, lock-order deadlock detection, chaos-obs coverage,
# import hygiene, donation safety, the metrics contract, trace discipline,
# commit discipline (crash consistency), thread lifecycle, and the env-lane
# wiring (rule catalog: docs/analysis.md)
python -m tosa

export JAX_PLATFORMS=cpu
if [[ "${XLA_FLAGS:-}" != *xla_force_host_platform_device_count* ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
fi

if [[ "$NATIVE_SANITIZE" == "1" ]]; then
  CXX="${CXX:-g++}"
  if ! command -v "$CXX" >/dev/null 2>&1; then
    echo "native-sanitize leg SKIPPED: no C++ toolchain ($CXX not found)"
    exit 0
  fi
  SAN_DIR="$(mktemp -d /tmp/tos_native_san.XXXXXX)"
  trap 'rm -rf "$SAN_DIR"' EXIT
  echo "native-sanitize leg: building ASan+UBSan libtfrecord_io.so in $SAN_DIR"
  "$CXX" -O1 -g -fPIC -std=c++17 -shared \
    -fsanitize=address,undefined -fno-sanitize-recover=all \
    -o "$SAN_DIR/libtfrecord_io.so" native/tfrecord_io.cc
  export TOS_NATIVE_LIB="$SAN_DIR/libtfrecord_io.so"
  # python itself is not ASan-instrumented, so the runtime must be preloaded;
  # leak checking is off because the interpreter "leaks" by design at exit
  ASAN_RT="$("$CXX" -print-file-name=libasan.so)"
  UBSAN_RT="$("$CXX" -print-file-name=libubsan.so)"
  export LD_PRELOAD="$ASAN_RT $UBSAN_RT"
  export ASAN_OPTIONS="detect_leaks=0:abort_on_error=1"
  exec python -m pytest tests/test_native_io.py tests/test_loader_pipeline.py -q \
    ${EXTRA[@]+"${EXTRA[@]}"}
fi

if python -c "import pyspark" 2>/dev/null; then
  echo "pyspark available: running with TOS_TEST_PYSPARK=1 (local-cluster[2,1,1024])"
  export TOS_TEST_PYSPARK=1
  export MASTER="local-cluster[2,1,1024]"
else
  echo "pyspark not installed: using the bundled local multi-process backend"
fi

if [[ "$MULTICHIP" == "1" ]]; then
  # mesh legs: hybrid-mesh placement and the 2-rank dp×tp gloo world
  # (tests/test_multichip.py), and the model-axis legs
  # (tests/test_model_axes.py): dp×tp, fsdp overlay and ring numeric-parity
  # gates on forced cpu devices
  exec python -m pytest tests/test_multichip.py tests/test_model_axes.py -q \
    -m "not chaos" ${EXTRA[@]+"${EXTRA[@]}"}
fi

if [[ "$PERF_SMOKE" == "1" ]]; then
  # covers the IO/parse overlap proof and the async-checkpoint overlap leg
  # (tests/test_ckpt_chaos.py::TestOverlap) — both sleep-staged, no real
  # accelerator or absolute-throughput assertion involved — and the
  # decode-plane GIL-release leg (tests/test_decode_plane.py::TestGilRelease:
  # the parse runs in the workers' own pids and fills the thread pool's
  # stream byte for byte; no clock)
  exec python -m pytest tests/ -q -m perf_smoke ${EXTRA[@]+"${EXTRA[@]}"}
fi

if [[ "$TRACE_SMOKE" == "1" ]]; then
  # tracing-plane end-to-end proof: a 1-executor train records flight shards
  # from every tier (driver, Spark executor, jax child), a benign one-shot
  # chaos fault forces a ring dump, and the merged Chrome trace must pass
  # schema validation with the lifecycle spans and the dump marker present
  # on one trace id.
  export TOS_TRACE_DIR="$(mktemp -d /tmp/tos_trace_smoke.XXXXXX)"
  export TOS_CHAOS_PLAN='{"seed": 7, "sites": {"feed.stall": {"probability": 1.0, "max_count": 1, "delay_s": 0.01}}}'
  echo "trace-smoke leg: recording under $TOS_TRACE_DIR"
  python -m pytest tests/test_trace_smoke.py -q
  python -m tensorflowonspark_tpu.obs.tracemerge --dir "$TOS_TRACE_DIR" \
    --check --summary \
    --require-span node_main --require-span feed_wave \
    --require-event flight_dump --require-same-trace
  echo "trace-smoke leg: merged Chrome trace at $TOS_TRACE_DIR/trace.json"
  exit 0
fi

if [[ "$CHAOS" == "1" ]]; then
  # recovery-ladder legs (first, before the benign env plan is exported —
  # each test installs its own single-victim plan): node.kill drives the
  # shrink direction (blacklist after repeated loss, shrink-to-fit
  # relaunch, resharded resume), and the once-latched preempt→drain→regrow
  # run drives the grow direction (mid-run regrow poll re-probes the
  # recovered victim, posts a preemption warning, the drained workers part
  # cleanly and the ladder relaunches at full size) — recovery counters
  # asserted from the merged cluster metrics in both.
  #
  # All ladder legs and the watchdog lease-expiry leg record into one
  # flight root on one pinned trace id (tracing.mint adopts TOS_TRACE_ID),
  # so the victim child's last spans, the watchdog's lease_expired verdict,
  # the regrow poll's elastic_regrow span, the children's preempt_drain
  # events, and the ladder's relaunch spans land on ONE causally-ordered
  # timeline — asserted post-hoc by tracemerge --check below.
  export TOS_TRACE_DIR="$(mktemp -d /tmp/tos_trace_chaos.XXXXXX)"
  export TOS_TRACE_ID="$(python -c 'import secrets; print(secrets.token_hex(16))')"
  echo "chaos leg: recovery-ladder runs: node.kill shrink + preempt-drain regrow (flight recording at $TOS_TRACE_DIR)"
  python -m pytest tests/test_elastic.py -q -m "chaos and slow"
  echo "chaos leg: watchdog lease-expiry run (same trace id)"
  python -m pytest "tests/test_watchdog.py::test_lease_expiry_names_the_executor_for_the_ledger" -q
  python -m tensorflowonspark_tpu.obs.tracemerge --dir "$TOS_TRACE_DIR" --check \
    --require-span node_main --require-span elastic_relaunch \
    --require-span elastic_regrow --require-event preempt_drain \
    --require-event lease_expired --require-same-trace
  echo "chaos leg: flight recording merged clean ($TOS_TRACE_DIR/trace.json)"
  unset TOS_TRACE_DIR TOS_TRACE_ID
  # control-plane leg (also self-installed plans): control.driver_crash
  # drops the membership registry mid-watch (after control.journal_tear
  # tore the manifest publish) — recovery replays the journal, re-adopts
  # every live lease with zero relaunches and a bumped epoch; plus the
  # benign control.lease_delay run. Asserted from merged cluster metrics.
  echo "chaos leg: control.driver_crash registry-recovery run"
  python -m pytest tests/test_chaos_control.py -q -m "chaos and slow"
  # serving-mesh leg (self-installed plan): serving.replica_kill SIGKILLs
  # one of three replicas under sustained client load — the router must
  # fail every affected request over (cluster.metrics() shows
  # serving_failovers_total > 0) with zero client-visible errors, the
  # replicas_active gauge dips and recovers, and the dead lease expires.
  echo "chaos leg: serving.replica_kill mesh-failover run"
  python -m pytest tests/test_chaos_mesh.py -q -m "chaos and slow"
  # text-plane leg (self-installed plans): data.tokenize_error swaps records
  # for invalid UTF-8 on a live cluster — the skips must be charged against
  # max_bad_records and surface as chaos_fault_data_tokenize_error_total /
  # text_tokenize_errors_total in the merged cluster metrics; data.pack_stall
  # delays inside packing and the stall classifier must call the job
  # input-bound.
  echo "chaos leg: text-plane tokenize_error/pack_stall run"
  python -m pytest tests/test_chaos_text.py -q -m chaos
  # store leg (self-installed plans): store.read_error must be absorbed by
  # the store retry budget with the stream byte-identical, store.remote_stall
  # must land in shard-read time (io_bound classification), and a
  # store.prefetch_tear'd staged shard must be rejected by verify-on-read
  # and re-fetched cold — all against the in-process HTTP fixture.
  echo "chaos leg: store read_error/remote_stall/prefetch_tear run"
  python -m pytest tests/test_store.py -q -m chaos
  # Benign-in-outcome sites at low probability: the suite's assertions
  # must keep passing — most sites only perturb timing; data.decode_kill
  # SIGKILLs a decode worker, which the plane's respawn-and-release
  # protocol must absorb without losing or duplicating a row. Error
  # faults get exercised deterministically by tests/test_chaos_*.py.
  export TOS_CHAOS_PLAN='{"seed": 2024, "sites": {
    "feed.stall":           {"probability": 0.02, "max_count": null, "delay_s": 0.01},
    "feed.slow_consumer":   {"probability": 0.02, "max_count": null, "delay_s": 0.01},
    "data.producer_delay":  {"probability": 0.05, "max_count": null, "delay_s": 0.01},
    "data.shard_read":      {"probability": 0.05, "max_count": null, "delay_s": 0.01},
    "data.decode_kill":     {"probability": 0.05, "max_count": null},
    "data.cache_tear":      {"probability": 0.05, "max_count": null},
    "data.readahead_stall": {"probability": 0.05, "max_count": null, "delay_s": 0.01},
    "data.pack_stall":      {"probability": 0.05, "max_count": null, "delay_s": 0.01},
    "store.read_error":     {"probability": 0.02, "max_count": null},
    "store.remote_stall":   {"probability": 0.05, "max_count": null, "delay_s": 0.01},
    "store.prefetch_tear":  {"probability": 0.05, "max_count": null},
    "serving.latency":      {"probability": 0.05, "max_count": null, "delay_s": 0.01},
    "reservation.slow_accept": {"probability": 0.05, "max_count": null, "delay_s": 0.01},
    "control.lease_delay":  {"probability": 0.05, "max_count": null, "delay_s": 0.005},
    "ckpt.snapshot_stall":  {"probability": 0.05, "max_count": null, "delay_s": 0.01},
    "ckpt.write_slow":      {"probability": 0.05, "max_count": null, "delay_s": 0.01}
  }}'
  export TOS_CHAOS_LOG="$(mktemp /tmp/tos_chaos_log.XXXXXX)"
  echo "chaos leg: plan active, fault log at $TOS_CHAOS_LOG"
  python -m pytest tests/ -q ${EXTRA[@]+"${EXTRA[@]}"}
  if [[ ! -s "$TOS_CHAOS_LOG" ]]; then
    echo "chaos leg FAILED: no faults were injected (empty $TOS_CHAOS_LOG)" >&2
    exit 1
  fi
  echo "chaos leg: $(wc -l < "$TOS_CHAOS_LOG") fault(s) injected"
  exit 0
fi

exec python -m pytest tests/ -q ${EXTRA[@]+"${EXTRA[@]}"}
