"""Merge per-process flight shards into one Chrome-trace-event JSON.

``python -m tensorflowonspark_tpu.obs.tracemerge --dir $TOS_TRACE_DIR --out
trace.json`` walks every shard under the trace root
(:func:`tensorflowonspark_tpu.obs.flight.list_shards`), aligns each shard's
wall clock onto the driver's, and emits a single ``{"traceEvents": [...]}``
document loadable by Perfetto / ``chrome://tracing``.

Clock alignment.  The driver's clock is the reference (offset 0).  Every
other shard resolves its offset in priority order: the lowest-RTT ``clock``
record journaled by :func:`tensorflowonspark_tpu.obs.tracing.observe_clock`
(NTP-style midpoint estimate from the reservation REG round-trip), else the
``clock_off`` carried by the newest segment ``meta`` header (inherited by
same-host children via ``TOS_TRACE_CLOCK_OFF``), else 0.

Track layout.  Each shard becomes one Chrome *process* (``M``
``process_name`` metadata from its ``meta`` header).  Context-manager spans
are emitted as matched ``B``/``E`` pairs on their recording thread's track;
retroactive spans carrying a ``track`` label
(:func:`tensorflowonspark_tpu.obs.tracing.record_span`) land on a track of
that name as ``X`` complete events.

Nesting repair.  Span starts are wall-clock but durations are monotonic
(NTP steps must not corrupt durations — see ``obs/trace.py``), so a child's
computed end can jitter past its parent's by microseconds.  Before emitting
``B``/``E`` pairs the merger clamps each span into its enclosing interval,
restoring a proper bracket sequence per track.
"""

import argparse
import json
import os
import sys

from tensorflowonspark_tpu.obs import flight

#: first synthetic Chrome tid of a shard's labelled tracks (real thread ids
#: are os-assigned and never this large on Linux, whose pid space caps at 2^22)
TRACK_TID_BASE = 9_000_000


def resolve_offset(records):
    """The shard's wall-clock offset onto driver time (seconds to add)."""
    best_off, best_rtt = None, None
    meta_off = 0.0
    for rec in records:
        kind = rec.get("kind")
        if kind == "clock":
            rtt = rec.get("rtt_s")
            if best_rtt is None or (rtt is not None and rtt < best_rtt):
                best_rtt = rtt
                best_off = rec.get("offset_s", 0.0)
        elif kind == "meta":
            meta_off = rec.get("clock_off", meta_off) or 0.0
    return float(best_off if best_off is not None else meta_off)


def _clamp_nesting(spans):
    """Clamp each span's end into its enclosing span so the B/E bracket
    sequence is well formed despite wall/monotonic micro-jitter."""
    spans = sorted(spans, key=lambda s: (s["_b"], -(s["_e"] - s["_b"])))
    stack = []
    for s in spans:
        while stack and s["_b"] >= stack[-1]["_e"]:
            stack.pop()
        if stack and s["_e"] > stack[-1]["_e"]:
            s["_e"] = stack[-1]["_e"]
        if stack and s["_b"] < stack[-1]["_b"]:
            s["_b"] = stack[-1]["_b"]
        stack.append(s)
    return spans


def _shard_events(records, pid, offset):
    """Chrome events for one shard (pid = synthetic process id)."""
    meta = next((r for r in records if r.get("kind") == "meta"), {})
    label = "{}:{} pid={}".format(
        meta.get("host", "?"), meta.get("proc", "?"), meta.get("pid", "?")
    )
    events = [
        {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
         "args": {"name": label}},
    ]
    track_tids = {}
    by_tid = {}
    for rec in records:
        kind = rec.get("kind")
        ts_us = (rec.get("ts", 0.0) + offset) * 1e6
        if kind == "span":
            track = rec.get("track")
            if track:
                tid = track_tids.get(track)
                if tid is None:
                    tid = track_tids[track] = TRACK_TID_BASE + len(track_tids)
                    events.append({
                        "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                        "args": {"name": track},
                    })
                events.append({
                    "ph": "X", "name": rec.get("name", "?"), "cat": track,
                    "pid": pid, "tid": tid, "ts": ts_us,
                    "dur": max(0.0, rec.get("dur_s", 0.0)) * 1e6,
                    "args": _span_args(rec),
                })
            else:
                tid = int(rec.get("tid", 0))
                by_tid.setdefault(tid, []).append({
                    "name": rec.get("name", "?"),
                    "_b": ts_us,
                    "_e": ts_us + max(0.0, rec.get("dur_s", 0.0)) * 1e6,
                    "args": _span_args(rec),
                })
        elif kind == "event":
            events.append({
                "ph": "i", "name": rec.get("name", "?"), "cat": "event",
                "pid": pid, "tid": 0, "ts": ts_us, "s": "p",
                "args": _span_args(rec),
            })
        elif kind == "dump":
            events.append({
                "ph": "i", "name": "flight_dump", "cat": "dump",
                "pid": pid, "tid": 0, "ts": ts_us, "s": "p",
                "args": {"reason": rec.get("reason", "?")},
            })
    for tid, spans in by_tid.items():
        for s in _clamp_nesting(spans):
            args = s["args"]
            events.append({"ph": "B", "name": s["name"], "pid": pid, "tid": tid,
                           "ts": s["_b"], "args": args, "_d": s["_e"] - s["_b"]})
            events.append({"ph": "E", "name": s["name"], "pid": pid, "tid": tid,
                           "ts": s["_e"], "_d": s["_e"] - s["_b"]})
    return events


def _span_args(rec):
    args = dict(rec.get("attrs") or {})
    for key in ("trace", "span", "parent", "ok"):
        if rec.get(key) is not None:
            args[key] = rec[key]
    return args


def _sort_key(evt):
    # per-track emit order: E before B at equal ts (close, then open);
    # among Bs the longer span opens first, among Es the shorter closes first
    ph = evt.get("ph")
    dur = evt.get("_d", 0.0)
    if ph == "E":
        return (evt.get("ts", 0.0), 0, dur)
    if ph == "B":
        return (evt.get("ts", 0.0), 1, -dur)
    return (evt.get("ts", 0.0), 1, 0.0)


def merge_directory(root):
    """Merge every shard under ``root``.

    Returns ``(trace, summary)`` — ``trace`` is the Chrome JSON document,
    ``summary`` a per-shard accounting (offsets, record/torn counts, trace
    ids seen).
    """
    events = []
    shards = []
    trace_ids = set()
    for pid, shard_dir in enumerate(flight.list_shards(root), start=1):
        records, torn = flight.read_shard(shard_dir)
        offset = resolve_offset(records)
        for rec in records:
            if rec.get("trace"):
                trace_ids.add(rec["trace"])
        shards.append({
            "shard": os.path.basename(shard_dir),
            "pid": pid,
            "records": len(records),
            "torn": torn,
            "clock_offset_s": offset,
        })
        events.extend(_shard_events(records, pid, offset))
    metas = [e for e in events if e.get("ph") == "M"]
    rest = sorted((e for e in events if e.get("ph") != "M"), key=_sort_key)
    for e in rest:
        e.pop("_d", None)
    trace = {"traceEvents": metas + rest, "displayTimeUnit": "ms"}
    summary = {
        "shards": shards,
        "events": len(metas) + len(rest),
        "trace_ids": sorted(trace_ids),
    }
    return trace, summary


def validate_chrome_trace(trace):
    """Validate the merged document against the Chrome trace-event schema
    subset the CI leg asserts: required keys per event, monotone ``ts`` per
    (pid, tid) track, and matched ``B``/``E`` pairs.  Returns a list of
    problem strings (empty = valid)."""
    problems = []
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    last_ts = {}
    stacks = {}
    for i, e in enumerate(events):
        ph = e.get("ph")
        if ph is None or "name" not in e or "pid" not in e:
            problems.append("event {}: missing required key (ph/name/pid)".format(i))
            continue
        if ph == "M":
            continue
        if "tid" not in e or "ts" not in e:
            problems.append("event {}: missing required key (tid/ts)".format(i))
            continue
        key = (e["pid"], e["tid"])
        if e["ts"] < last_ts.get(key, float("-inf")):
            problems.append(
                "event {}: ts {} not monotone on track {}".format(i, e["ts"], key)
            )
        last_ts[key] = e["ts"]
        if ph == "B":
            stacks.setdefault(key, []).append(e["name"])
        elif ph == "E":
            stack = stacks.setdefault(key, [])
            if not stack:
                problems.append("event {}: E {!r} with empty stack".format(i, e["name"]))
            elif stack[-1] != e["name"]:
                problems.append(
                    "event {}: E {!r} does not match open B {!r}".format(
                        i, e["name"], stack[-1]
                    )
                )
                stack.pop()
            else:
                stack.pop()
        elif ph == "X":
            if e.get("dur", 0) < 0:
                problems.append("event {}: negative dur".format(i))
    for key, stack in stacks.items():
        if stack:
            problems.append("track {}: unclosed B spans {}".format(key, stack))
    return problems


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m tensorflowonspark_tpu.obs.tracemerge",
        description="merge flight-recorder shards into one Chrome trace JSON",
    )
    parser.add_argument("--dir", default=os.environ.get(flight.TRACE_DIR_ENV),
                        help="trace root (default: $TOS_TRACE_DIR)")
    parser.add_argument("--out", default=None,
                        help="output path (default: <dir>/trace.json)")
    parser.add_argument("--check", action="store_true",
                        help="validate the merged trace; exit 1 on schema problems")
    parser.add_argument("--summary", action="store_true",
                        help="print the merge summary JSON to stdout")
    parser.add_argument("--require-span", action="append", default=[],
                        metavar="NAME", help="fail unless a span NAME is present")
    parser.add_argument("--require-event", action="append", default=[],
                        metavar="NAME", help="fail unless an instant event NAME is present")
    parser.add_argument("--require-same-trace", action="store_true",
                        help="fail unless every shard record shares one trace_id")
    args = parser.parse_args(argv)
    if not args.dir:
        parser.error("--dir not given and TOS_TRACE_DIR unset")
    trace, summary = merge_directory(args.dir)
    out = args.out or os.path.join(args.dir, "trace.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(trace, f)
    print("tracemerge: {} events from {} shard(s) -> {}".format(
        summary["events"], len(summary["shards"]), out))
    failures = []
    if args.check:
        failures.extend(validate_chrome_trace(trace))
    names = {(e.get("ph"), e.get("name")) for e in trace["traceEvents"]}
    spans_present = {n for ph, n in names if ph in ("B", "X")}
    events_present = {n for ph, n in names if ph == "i"}
    for want in args.require_span:
        if want not in spans_present:
            failures.append("required span {!r} not present".format(want))
    for want in args.require_event:
        if want not in events_present:
            failures.append("required event {!r} not present".format(want))
    if args.require_same_trace and len(summary["trace_ids"]) != 1:
        failures.append(
            "expected exactly one trace_id, saw {}".format(summary["trace_ids"])
        )
    if args.summary:
        print(json.dumps(summary, sort_keys=True))
    if failures:
        for f in failures:
            print("tracemerge FAILED: {}".format(f), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
