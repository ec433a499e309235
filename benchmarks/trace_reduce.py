"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read: device busy and idle time, time per device operation, the
longest idle gaps named by what the host was doing, time per named kernel,
and collective time not hidden behind compute.

On a TPU every chip is a plane ``/device:TPU:<n>`` whose ``XLA Ops`` line
holds one event per executed HLO instruction, named by the instruction's
whole text (``%fusion.3 = f32[...] fusion(...)``); asynchronous copies and
collectives run beside them on ``Async XLA Ops``, which counts towards
collective time but not towards busy time. The CPU backend (rehearsals and
the recorded test trace) has no device plane: its executed instructions are
the events that carry an ``hlo_op`` stat on the host plane's XLA client
threads, and they stand in as one "device". Host spans are the
``TraceAnnotation`` events named ``bench.*`` on the host plane; the traced
window runs from the first of them to the end of the last.
"""

import collections
import glob
import os
import re

HOST_SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
_CUSTOM_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom_call_target=\"tpu_custom_call\"")
_KERNEL = re.compile(r'op_name="[^"]*?([^/"]+)/pallas_call')


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under {}".format(trace_dir))
    return found[-1]


def load(path):
    """The trace as plain data: ``{"devices": {plane: [(name, start, end)]},
    "async": {plane: [...]}, "host": [(name, start, end)]}``, seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, background, host, cpu_ops = {}, {}, [], []

    def spans(plane, line_name):
        return [
            (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for ln in plane.lines if ln.name == line_name for e in ln.events
        ]

    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = spans(plane, "XLA Ops")
            background[plane.name] = spans(plane, "Async XLA Ops")
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    span = (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append(span)
                    elif any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append(span)
    if not devices and cpu_ops:
        devices["/host:CPU (XLA client threads)"] = cpu_ops
    return {"devices": devices, "async": background, "host": sorted(host, key=lambda s: s[1])}


def union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """The part of merged ``intervals`` not covered by merged ``holes``."""
    out = []
    for a, b in intervals:
        cursor = a
        for ha, hb in holes:
            if hb <= cursor or ha >= b:
                continue
            if ha > cursor:
                out.append((cursor, ha))
            cursor = max(cursor, hb)
        if cursor < b:
            out.append((cursor, b))
    return out


def kernel_names(hlo_text):
    """``{HLO instruction: Pallas kernel name}`` of a compiled module's Mosaic
    custom calls (the kernel's ``name=`` is the last scope of ``op_name``)."""
    names = {}
    for line in (hlo_text or "").splitlines():
        call = _CUSTOM_CALL.match(line)
        kernel = _KERNEL.search(line)
        if call and kernel:
            names[call.group(1)] = kernel.group(1)
    return names


def _host_label(gap, host):
    """The host span that covers most of ``gap``."""
    best, covered = "no bench span", 0.0
    for name, start, end in host:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > covered:
            best, covered = name, overlap
    return best


def reduce_trace(trace, hlo_text=None, top=10):
    host, devices = trace["host"], trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device operation")
    if host:
        lo, hi = host[0][1], max(end for _, _, end in host)
    else:
        lo = min(s for ops in devices.values() for _, s, _ in ops)
        hi = max(e for ops in devices.values() for _, _, e in ops)
    kernels = kernel_names(hlo_text)
    known = sorted(set(kernels.values()), key=len, reverse=True)
    n = len(devices)
    busy_s = collective_s = exposed_s = 0.0
    op_s, op_n, kernel_s = collections.Counter(), collections.Counter(), collections.Counter()
    gaps = []
    for plane, ops in devices.items():
        inside = [(name, max(s, lo), min(e, hi)) for name, s, e in ops if min(e, hi) > max(s, lo)]
        busy = union([(s, e) for _, s, e in inside])
        busy_s += total(busy) / n
        compute = union([(s, e) for name, s, e in inside if not COLLECTIVE.match(name)])
        beside = [(name, s, e) for name, s, e in trace.get("async", {}).get(plane, [])]
        collectives = union([(max(s, lo), min(e, hi)) for name, s, e in inside + beside
                             if COLLECTIVE.match(name) and min(e, hi) > max(s, lo)])
        collective_s += total(collectives) / n
        exposed_s += total(subtract(collectives, compute)) / n
        for text, s, e in inside:
            bare = text.split(" = ", 1)[0].lstrip("%")
            kernel = kernels.get(bare) or next((k for k in known if k in text), None)
            if kernel:
                kernel_s[kernel] += (e - s) / n
            # a kernel's calls (one per layer) count as one operation
            op_s[kernel or bare] += (e - s) / n
            op_n[kernel or bare] += 1
        gaps += subtract([(lo, hi)], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": hi - lo, "busy_s": busy_s, "devices": n,
        "collective_s": collective_s, "collective_exposed_s": exposed_s,
        "kernel_s": dict(kernel_s),
        "device_ops": [["{}_x{}".format(name, op_n[name]), secs] for name, secs in op_s.most_common(top)],
        "idle_gaps": [[_host_label(g, host), g[1] - g[0]] for g in gaps[:top]],
        "host_span_s": {
            name: sum(e - s for nm, s, e in host if nm == name) for name in {nm for nm, _, _ in host}
        },
    }


def reduce_dir(trace_dir, hlo_text=None):
    return reduce_trace(load(newest_xplane(trace_dir)), hlo_text=hlo_text)
