"""What runs inside the jax child that holds the cell's chips: set-up, the
first checked steps, warm-up, the measured window, the traced steps and the
comparison with the reference. ``run.py`` launches ``main_fun`` through
``TFCluster.run``; the job itself (model, state, compiled step, input
pipeline) comes from ``families/<family>.py``.

One ``advance()`` is the whole of a step as the window drives it — take a
batch from the feed (``bench.next_batch``), dispatch the compiled step
(``bench.dispatch``) — and set-up, warm-up, window and trace all go through
it, on one state object. At most two steps are in flight: the host waits for
step i-2 before it dispatches step i, so the window ends within a step or two
of ``--seconds`` without the device ever running dry.
"""

import collections
import importlib
import json
import os
import time
import traceback

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
IN_FLIGHT = 2


class Loop:
    """The step loop over one job: the same call and feed from the first
    checked step to the last traced one."""

    def __init__(self, job):
        import jax

        self.job, self.jax = job, jax
        self.state, self.metrics = job.state, None
        self.pending = collections.deque()
        self.steps = 0
        self.span_s = collections.Counter()
        self.batch_struct = None
        self.step_times = []

    def span(self, name):
        return _Span(self, name)

    def advance(self):
        with self.span("bench.next_batch"):
            batch = next(self.job.batches)
        with self.span("bench.dispatch"):
            if len(self.pending) >= IN_FLIGHT:
                self.jax.block_until_ready(self.pending.popleft())
            if self.batch_struct is None:
                self.batch_struct = self.jax.tree.map(_struct, batch)
            self.state, self.metrics = self.job.step(self.state, batch)
            self.pending.append(self.metrics["loss"])
        self.steps += 1
        self.step_times.append(time.perf_counter())

    def fence(self):
        with self.span("bench.fence"):
            self.jax.block_until_ready(self.metrics["loss"])
            self.pending.clear()


class _Span:
    def __init__(self, loop, name):
        self.loop, self.name = loop, name
        self.annotation = loop.jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.annotation.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.loop.span_s[self.name] += time.perf_counter() - self.t0
        return self.annotation.__exit__(*exc)


def _snapshot(loop, compiles):
    from tensorflowonspark_tpu import obs

    registry = obs.snapshot()
    return {
        "t": time.perf_counter(), "steps": loop.steps, "spans": dict(loop.span_s),
        "counts": dict(loop.job.counts), "compiles": len(compiles),
        "counters": {k: _value(v) for k, v in registry["counters"].items()},
        "gauges": {k: _value(v) for k, v in registry["gauges"].items()},
    }


def _value(entry):
    """A registry snapshot entry's number (labelled series are summed)."""
    if isinstance(entry, dict):
        value = entry.get("value", entry.get("values"))
        if isinstance(value, dict):
            return sum(v for v in value.values() if isinstance(v, (int, float)))
        return value
    return entry


def _delta(a, b):
    """What happened between two snapshots."""
    def sub(x, y):
        return {k: v - y.get(k, 0) for k, v in x.items() if isinstance(v, (int, float))}

    return {
        "seconds": b["t"] - a["t"], "steps": b["steps"] - a["steps"],
        "spans": sub(b["spans"], a["spans"]), "counts": sub(b["counts"], a["counts"]),
        "compiles": b["compiles"] - a["compiles"], "counters": sub(b["counters"], a["counters"]),
        "gauges": b["gauges"],
    }


def checked_steps(loop, n, parts):
    """Drive the job's first ``n`` steps and read what the reference is held
    against: every loss, the first gradient's norm and sketch per leaf (from
    the optimizer's state after step one) and, after step ``n``, the norm per
    leaf of the parameters' change. The first step is timed apart: it compiles, or
    loads from the cache."""
    t0 = time.perf_counter()
    loop.advance()
    loop.fence()
    parts["first_step_s"] = time.perf_counter() - t0
    norms, sketches = loop.job.first_grad(loop.state)
    first = {"losses": [float(loop.metrics["loss"])], "first_grad": norms, "first_grad_sketch": sketches}
    for _ in range(n - 1):
        loop.advance()
        first["losses"].append(float(loop.metrics["loss"]))
    first["param_change"] = loop.job.param_change(loop.state)
    return first


def release(loop):
    """Stop the job's feed and drop its state, so that the reference never
    shares the chip with the program; returns the batches the checked steps
    consumed and the reference that will follow them."""
    job = loop.job
    kept, reference = list(job.kept), job.reference
    job.close()
    loop.state = loop.metrics = job.state = None
    loop.pending.clear()
    return kept, reference


def measure(loop, seconds, compiles):
    """The window: steps for ``seconds`` by the host's clock, the clock
    stopped after ``block_until_ready`` on the last step."""
    loop.fence()
    before = _snapshot(loop, compiles)
    deadline = before["t"] + seconds
    while time.perf_counter() < deadline:
        loop.advance()
    loop.fence()
    window = _delta(before, _snapshot(loop, compiles))
    # dispatch times of the window's steps, from its start: step i is
    # dispatched when step i-2 has finished, so these pace with the device
    window["dispatch_at"] = [t - before["t"] for t in loop.step_times[before["steps"]:]]
    window["loadavg_end"] = os.getloadavg()[0]
    return window


def traced(loop, steps, trace_dir):
    """A few steady steps under the profiler; returns the host's window.

    The steps' batches are taken from the feed and placed on the device
    before the profiler starts: with the image feed's 38 MB host-to-device
    copies inside the traced window, every traced run stalled for 0.5-2.5 s
    (five of five; my chip runs, PR 23) and the device's idle share read
    60-85% where the untraced rate says under 1%. What the input costs is
    read from the untraced window instead (``*_input_wait_pct``)."""
    import jax

    loop.fence()
    ready = [next(loop.job.batches) for _ in range(steps)]
    jax.block_until_ready(ready)
    loop.job.batches = _chain(ready, loop.job.batches)
    # the Python tracer hooks every call of every thread, the input pipeline's
    # among them: the host's TraceMe spans are enough
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    try:
        for _ in range(steps):
            loop.advance()
        loop.fence()
        window_s = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    return window_s


def device_block(jax, extra=None):
    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    block = {
        "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
        "memory_peak_bytes": int(max(peaks + [0])),
    }
    block.update(extra or {})
    return block


def run_job(spec, ctx, t_enter, build=None, out=print):
    """Everything between entering the child and its result dict. ``build``
    replaces the family's own (tests hand in a broken job)."""
    import jax

    from benchmarks import check, trace_reduce

    parts = {}
    t0 = time.perf_counter()
    devices = jax.devices()
    parts["backend_init_s"] = time.perf_counter() - t0
    platform, kind = devices[0].platform, devices[0].device_kind
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        peaks = json.load(f)
    if not spec["rehearse"]:
        if platform != "tpu" or len(devices) < spec["chips"]:
            raise RuntimeError("cell {} needs {} TPU chip(s); jax found {} x {}".format(
                spec["workload"], spec["chips"], len(devices), platform))
        if kind not in peaks:
            raise RuntimeError("device kind {!r} is not in peaks.json".format(kind))

    t0 = time.perf_counter()
    jax.block_until_ready(jax.numpy.zeros((8, 128)) + 1)
    parts["first_op_s"] = time.perf_counter() - t0
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs) if event == COMPILE_EVENT else None)

    if build is None:
        build = importlib.import_module("benchmarks.families." + spec["config"]["family"]).build
    job = build(spec, ctx, parts)
    loop = Loop(job)
    traffic = spec["traffic"]

    first = checked_steps(loop, traffic["check_steps"], parts)
    for _ in range(traffic["warmup_steps"]):
        loop.advance()
    loop.fence()
    parts["compiles_in_setup"] = len(compiles)

    t_window = time.time()
    window = measure(loop, spec["seconds"], compiles)
    window["units"] = window["steps"] * job.units_per_step
    window["rate_per_chip"] = window["units"] / window["seconds"] / job.chips
    window["flops_per_step"] = job.flops_per_step(window["counts"])
    step_memory = _step_memory(job, loop)
    extra, trace = {}, None
    if spec["trace"]:
        trace_dir = os.path.join(spec["scratch"], "trace")
        host_window_s = traced(loop, traffic["trace_steps"], trace_dir)
        trace = trace_reduce.reduce_dir(trace_dir, hlo_text=job.compiled.as_text())
        trace["steps"], trace["host_window_s"] = traffic["trace_steps"], host_window_s
        extra = {"busy_s": trace["busy_s"], "window_s": trace["window_s"]}
    device = device_block(jax, extra)
    device["memory_peak_bytes"] = max(device["memory_peak_bytes"], int(step_memory["total_bytes"]))
    last_loss = float(loop.metrics["loss"])

    # memory_peak_bytes above is the program's alone: its state goes first
    kept, reference = release(loop)
    del loop, job
    t0 = time.perf_counter()
    want = reference(kept)
    parts["reference_s"] = time.perf_counter() - t0
    read = check.readings(first, want)
    correct, lines = check.judge(read, check.load_limits(spec["workload"]))
    finite = last_loss == last_loss and abs(last_loss) != float("inf")
    lines.append("check last loss finite: {!r} {}".format(last_loss, "ok" if finite else "FAILED"))
    for line in lines:
        out(line)
    return {
        # a rehearsal is never a measurement: it reports the comparison's
        # verdict apart and `correct` false
        "check_ok": bool(correct and finite),
        "correct": bool(correct and finite and not spec["rehearse"]),
        "attempted": int(window["steps"]), "failed": 0 if finite else int(window["steps"]),
        "device": device, "t_enter": t_enter, "t_window": t_window, "parts": parts,
        "window": window, "trace": trace, "step_memory": step_memory,
        "check": read, "program_losses": first["losses"], "reference_losses": want["losses"],
        "peak": peaks.get(kind),
    }


def _chain(head, tail):
    yield from head
    yield from tail


def _struct(x):
    import jax

    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)


def _step_memory(job, loop):
    """The compiled step's own account of device memory: arguments + outputs
    + temporaries - aliased (``memory_stats`` counts live buffers only)."""
    import jax

    compiled = job.step.lower(jax.tree.map(_struct, loop.state), loop.batch_struct).compile()
    job.compiled = compiled
    m = compiled.memory_analysis()
    fields = {name: int(getattr(m, name, 0) or 0) for name in (
        "argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
        "alias_size_in_bytes", "generated_code_size_in_bytes")}
    fields["total_bytes"] = (
        fields["argument_size_in_bytes"] + fields["output_size_in_bytes"]
        + fields["temp_size_in_bytes"] - fields["alias_size_in_bytes"])
    return fields


def main_fun(args, ctx):
    """``TFCluster.run``'s ``main_fun``: run the job, write the result (or the
    error) where ``run.py`` reads it."""
    t_enter = time.time()
    spec = args
    result_path = os.path.join(spec["scratch"], "result.json")
    try:
        result = run_job(spec, ctx, t_enter)
    except BaseException:
        result = {"error": traceback.format_exc()}
        with open(result_path, "w") as f:
            json.dump(result, f)
        raise
    with open(result_path, "w") as f:
        json.dump(result, f)
