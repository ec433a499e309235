"""What the readers of the program's own spans and counters share: the
window's counter deltas and the gauges as they stood at its end (``child.py``
snapshots the program's registry), and the device time of the traced steps by
phase, from the run's own ``.xplane.pb``.

A program that does not count (the parent of the PR that brought these) has
no such counter or gauge, and its step's operations carry no ``tos.`` scope:
every reader then returns None.

**Phases.** ``compile_train_step`` names the step's two halves with
``jax.named_scope``, so a device operation's ``op_name`` says what it is for:
under ``tos.optimizer`` the optimizer; otherwise, holding
``rematted_computation``, a block computed again for the backward pass;
holding ``transpose(jvp(``, the backward pass; else under
``tos.loss_and_grad`` the forward pass (with the loss); anything else
(``other``: the step counter, copies the compiler adds outside both scopes).
A fusion carries the ``op_name`` of its root, so a phase is the phase of each
fusion's root. XLA fuses the optimizer's update into the fusions that
produce the weight gradients: its time is booked under ``bwd``, what is left
under ``opt`` is a remnant (0.2% and 0.008% of busy time in the two cells),
and no metric reads it.
"""

import os

from benchmarks import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PHASES = ("fwd", "recompute", "bwd", "opt", "other")


def counter(run, name):
    return run["window"]["counters"].get(name)


def gauge(run, name):
    return run["window"].get("gauges", {}).get(name)


def window_pct(run, name):
    """A ``*_seconds_total`` counter's share of the window."""
    seconds = counter(run, name)
    return None if seconds is None else 100.0 * seconds / run["window"]["seconds"]


def phase_of(op_name):
    if "tos.optimizer" in op_name:
        return "opt"
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(jvp(" in op_name:
        return "bwd"
    if "tos.loss_and_grad" in op_name:
        return "fwd"
    return "other"


#: ``tsl/profiler/protobuf/xplane.proto`` as ``{message: {field number: name}}``
#: (``benchmarks/tests/test_program_readers.py`` holds it against the schema
#: the installation ships). A field outside it stops the reader: a changed
#: schema is to be read about in a traceback, not in a metric that moved.
SCHEMA = {
    "XSpace": {1: "planes", 2: "errors", 3: "warnings", 4: "hostnames"},
    "XPlane": {1: "id", 2: "name", 3: "lines", 4: "event_metadata", 5: "stat_metadata", 6: "stats"},
    "XLine": {1: "id", 2: "name", 3: "timestamp_ns", 4: "events", 9: "duration_ps", 10: "display_id",
              11: "display_name"},
    "XEvent": {1: "metadata_id", 2: "offset_ps", 3: "duration_ps", 4: "stats", 5: "num_occurrences"},
    "XStat": {1: "metadata_id", 2: "double_value", 3: "uint64_value", 4: "int64_value", 5: "str_value",
              6: "bytes_value", 7: "ref_value"},
    "XEventMetadata": {1: "id", 2: "name", 3: "metadata", 4: "display_name", 5: "stats", 6: "child_id"},
    "XStatMetadata": {1: "id", 2: "name", 3: "description"},
    "MapEntry": {1: "key", 2: "value"},
}


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, message):
    """``(field name, value)`` of one protobuf ``message``: a varint as an
    int, a length-delimited field as a slice of ``buf``, a fixed one as its
    bytes. Raises ValueError on a field or wire type the schema lacks."""
    names = SCHEMA[message]
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError("wire type {} in an {}".format(wire, message))
            value, i = buf[i:i + size], i + size
        if key >> 3 not in names:
            raise ValueError("field {} in an {}: not in xplane.proto as read here".format(key >> 3, message))
        yield names[key >> 3], value


def _text(value):
    return bytes(value).decode("utf-8", "replace")


def _message(buf, message):
    """A message's singular fields as a dict, its repeated ones as lists."""
    out = {}
    for name, value in _fields(buf, message):
        if name in ("lines", "events", "stats", "event_metadata", "stat_metadata"):
            out.setdefault(name, []).append(value)
        else:
            out[name] = value
    return out


def _stats(raw_stats, stat_names):
    """``{stat name: text}`` of the ``XStat``s that hold a string, written
    out or as a reference to a stat's name."""
    out = {}
    for raw in raw_stats:
        stat = _message(raw, "XStat")
        name = stat_names.get(stat.get("metadata_id"))
        if name is None:
            continue
        if "str_value" in stat:
            out[name] = _text(stat["str_value"])
        elif "ref_value" in stat:
            out[name] = stat_names.get(stat["ref_value"], "")
    return out


def read_planes(path):
    """An ``.xplane.pb`` as plain data, ``{plane: {line: [(event name, start,
    end, stats)]}}`` in seconds, read from the file's own bytes.

    ``jax.profiler.ProfileData`` shows an event's own stats only. What an
    operation *is* — its ``tf_op`` (the ``op_name``), category, operations
    and bytes — the profiler keeps once, in the event's metadata, which that
    reader does not show; ``stats`` here holds both (strings only)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for name, raw_plane in _fields(space, "XSpace"):
        if name != "planes":
            continue
        plane = _message(raw_plane, "XPlane")
        if not plane.get("lines"):
            continue
        stat_names = {}
        for raw in plane.get("stat_metadata", []):
            entry = _message(raw, "MapEntry")
            stat_names[entry["key"]] = _text(_message(entry["value"], "XStatMetadata").get("name", b""))
        metadata = {}
        for raw in plane.get("event_metadata", []):
            entry = _message(raw, "MapEntry")
            meta = _message(entry["value"], "XEventMetadata")
            metadata[entry["key"]] = (_text(meta.get("name", b"")), _stats(meta.get("stats", []), stat_names))
        out = planes.setdefault(_text(plane.get("name", b"")), {})
        for raw_line in plane["lines"]:
            line = _message(raw_line, "XLine")
            t0 = line.get("timestamp_ns", 0) * 1e-9
            rows = out.setdefault(_text(line.get("name", b"")), [])
            for raw_event in line.get("events", []):
                event = _message(raw_event, "XEvent")
                event_name, stats = metadata.get(event.get("metadata_id", 0), ("", {}))
                if "stats" in event:
                    stats = dict(stats, **_stats(event["stats"], stat_names))
                start = t0 + event.get("offset_ps", 0) * 1e-12
                rows.append((event_name, start, start + event.get("duration_ps", 0) * 1e-12, stats))
    return planes


def load_device_ops(path, op_names=None):
    """``({plane: [(op_name, start, end)]}, (lo, hi))`` of a trace: every
    executed operation with its ``op_name``, and the window from the first
    ``bench.*`` host span to the end of the last.

    On a TPU the operations are the events of each ``/device:TPU:<n>``
    plane's ``XLA Ops`` line, and their ``tf_op`` stat is the ``op_name``.
    The CPU backend (the recorded test trace) has no device plane and its
    events carry only ``hlo_op``, the instruction's name: ``op_names`` maps
    those to ``op_name``s, from the compiled text."""
    devices, cpu_ops, host = {}, [], []
    for plane, lines in read_planes(path).items():
        if plane.startswith("/device:TPU:"):
            devices[plane] = [(stats.get("tf_op", ""), start, end)
                              for _, start, end, stats in lines.get("XLA Ops", [])]
        elif plane == "/host:CPU":
            for events in lines.values():
                for name, start, end, stats in events:
                    if name.startswith(trace_reduce.HOST_SPAN_PREFIX):
                        host.append((start, end))
                    elif "hlo_op" in stats:
                        cpu_ops.append(((op_names or {}).get(stats["hlo_op"], ""), start, end))
    if not devices and cpu_ops:
        devices["/host:CPU (XLA client threads)"] = cpu_ops
    window = (min(s for s, _ in host), max(e for _, e in host)) if host else None
    return devices, window


def phase_shares(devices, window=None):
    """``{phase: % of device busy time}`` (mean over chips), or None where no
    operation is under a ``tos.`` scope. An operation's time is its own
    duration; busy time is the union of all of them, as ``trace_reduce``
    takes it."""
    lo, hi = window or (float("-inf"), float("inf"))
    seconds = dict.fromkeys(PHASES, 0.0)
    busy = 0.0
    scoped = False
    for ops in devices.values():
        inside = [(name, max(s, lo), min(e, hi)) for name, s, e in ops if min(e, hi) > max(s, lo)]
        busy += trace_reduce.total(trace_reduce.union([(s, e) for _, s, e in inside]))
        for name, s, e in inside:
            scoped = scoped or "tos." in name
            seconds[phase_of(name)] += e - s
    if not scoped or not busy:
        return None
    return {phase: 100.0 * secs / busy for phase, secs in seconds.items()}


def phase_pct(run, phase):
    """One phase's share in the run's own trace (``run.py`` leaves it under
    ``.bench_scratch/<cell>/trace``), read once for all the phase readers."""
    if not run.get("trace"):
        return None
    if "_phase_shares" not in run:
        trace_dir = os.path.join(ROOT, ".bench_scratch", run["workload"], "trace")
        try:
            path = trace_reduce.newest_xplane(trace_dir)
        except FileNotFoundError:
            run["_phase_shares"] = None
        else:
            run["_phase_shares"] = phase_shares(*load_device_ops(path))
    shares = run["_phase_shares"]
    return None if shares is None else shares[phase]
