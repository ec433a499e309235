"""The program's spans on the profiler's clock (PR 24): ``obs.span`` as a
``tos.<name>`` annotation in a ``jax.profiler`` trace, per-step spans that
leave only their counter behind, the placement / step-dispatch / stall
counters, the compile listener's gauges, and the named scopes that let a
device operation's ``op_name`` say its phase."""

import glob
import json
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.layer_metrics import _program
from tensorflowonspark_tpu import obs, parallel, util
from tensorflowonspark_tpu.obs import flight, registry, tracing
from tensorflowonspark_tpu.train import SyncDataParallel, strategy as strategy_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(dp=1):
    return parallel.build_mesh({"dp": dp}, devices=jax.devices()[:dp])


def _value(name):
    snap = obs.snapshot()
    entry = snap["counters"].get(name) or snap["gauges"].get(name) or {"value": 0.0}
    return entry["value"]


def _linear_loss(params, batch):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)


def _linear_job(donate=True):
    strategy = SyncDataParallel(_mesh())
    optimizer = optax.sgd(0.1)
    state = strategy.create_state(lambda: {"w": jnp.zeros((4, 1))}, optimizer)
    step = strategy.compile_train_step(_linear_loss, optimizer, donate=donate)
    batch = strategy.shard_batch({"x": np.ones((8, 4), np.float32), "y": np.ones((8, 1), np.float32)})
    return strategy, state, step, batch


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for line in host.lines for e in line.events if e.name.startswith("tos.")]


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    """Lifecycle span, per-step span and the step's dispatch, all inside one
    profiler session: each is a ``tos.<name>`` event of the ``.xplane.pb``'s
    host plane, the per-step ones inside the enclosing one's interval."""
    _, state, step, batch = _linear_job()
    state, metrics = step(state, batch)  # compile outside the session
    jax.block_until_ready(metrics)
    reg = registry.Registry()
    seconds = reg.counter("unit_seconds_total")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("unit_lifecycle", registry=reg, nodes=1):
            with obs.span("unit_per_step", registry=reg, seconds_total=seconds):
                state, metrics = step(state, batch)
                jax.block_until_ready(metrics)
    finally:
        jax.profiler.stop_trace()
    events = {name: (lo, hi, stats) for name, lo, hi, stats in _host_events(str(tmp_path))}
    assert {"tos.unit_lifecycle", "tos.unit_per_step", "tos.step_dispatch"} <= set(events)
    outer, inner, dispatch = (events["tos." + n] for n in ("unit_lifecycle", "unit_per_step", "step_dispatch"))
    assert outer[0] <= inner[0] <= dispatch[0] and dispatch[1] <= inner[1] <= outer[1]
    # the dispatch is a step annotation: it carries its step number
    assert dispatch[2].get("step_num") == 2


def test_a_process_without_jax_opens_spans_without_importing_it():
    code = (
        "import sys\n"
        "from tensorflowonspark_tpu import obs\n"
        "c = obs.counter('unit_seconds_total')\n"
        "with obs.span('unit_lifecycle'):\n"
        "    with obs.span('unit_per_step', seconds_total=c):\n"
        "        pass\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n"
        "assert c.value > 0 and [e['span'] for e in obs.get_registry().events()] == ['unit_lifecycle']\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stderr


def test_per_step_spans_leave_the_lifecycle_events_in_place():
    reg = registry.Registry()
    seconds = reg.counter("unit_seconds_total")
    with obs.span("unit_lifecycle", registry=reg, nodes=4):
        pass
    for _ in range(2 * registry.MAX_EVENTS):
        with obs.span("unit_per_step", registry=reg, seconds_total=seconds) as sp:
            pass
    snap = reg.snapshot()
    assert [e["span"] for e in snap["events"]] == ["unit_lifecycle"]
    assert "obs_events_dropped_total" not in snap["counters"]
    assert set(snap["histograms"]) == {"unit_lifecycle_seconds"}
    assert seconds.value > 0 and sp.dur_s > 0


def test_per_step_span_takes_an_id_only_while_a_flight_shard_is_open(tmp_path, monkeypatch):
    reg = registry.Registry()
    seconds = reg.counter("unit_seconds_total")
    tracing.reset()
    try:
        monkeypatch.setenv(flight.TRACE_DIR_ENV, str(tmp_path))
        tracing.mint(proc="unit")
        assert flight.is_open()
        with obs.span("unit_per_step", registry=reg, seconds_total=seconds) as with_shard:
            pass
        shard = flight.current().shard_dir
        tracing.reset()
        assert not flight.is_open()
        with obs.span("unit_per_step", registry=reg, seconds_total=seconds) as without:
            pass
    finally:
        tracing.reset()
    assert with_shard._span_id is not None and without._span_id is None
    records, _ = flight.read_shard(shard)
    assert [r["name"] for r in records if r.get("kind") == "span"] == ["unit_per_step"]
    assert reg.events() == []


def test_disabled_collection_records_nothing():
    """``TOS_OBS=0``: a span is the shared no-op, nothing is counted, and the
    step callable still steps."""
    _, state, step, batch = _linear_job()
    before = {n: _value(n) for n in ("train_steps_dispatched_total", "h2d_place_bytes_total")}
    obs.set_enabled(False)
    try:
        assert obs.span("unit_lifecycle") is obs.span("unit_per_step", seconds_total=obs.counter("unit_seconds_total"))
        assert obs.span("unit_lifecycle").dur_s == 0.0
        for _ in range(3):
            state, metrics = step(state, batch)
        parallel.shard_batch({"x": np.ones((8, 4), np.float32)}, _mesh())
        events = len(obs.get_registry().events())
    finally:
        obs.set_enabled(True)
    assert int(state.step) == 3 and events == len(obs.get_registry().events())
    assert before == {n: _value(n) for n in before}


def test_shard_batch_counts_the_bytes_it_places():
    mesh = _mesh(2)
    batch = {"image": np.zeros((4, 8, 8, 3), np.uint8), "label": np.zeros((4,), np.int32)}
    bytes0, seconds0 = _value("h2d_place_bytes_total"), _value("h2d_place_seconds_total")
    placed = parallel.shard_batch(batch, mesh)
    assert placed["image"].sharding.spec[0] == "dp"
    assert _value("h2d_place_bytes_total") - bytes0 == 4 * 8 * 8 * 3 + 4 * 4
    assert _value("h2d_place_seconds_total") > seconds0


@pytest.mark.parametrize("factor,stalls", [(5.0, 1), (2.0, 0), (1.5, 0)])
def test_stall_meter_on_a_scripted_sequence(factor, stalls):
    """Steady, one interval of ``factor`` times the pace, steady again: one
    stall and its excess over the median when the factor is over two, else
    none — and the long interval does not make its neighbours look short."""
    meter = strategy_mod.StallMeter()
    pace = 0.1
    script = [pace] * 40 + [factor * pace] + [pace] * 40
    excesses = [meter.note(interval) for interval in script]
    found = [e for e in excesses if e]
    assert len(found) == stalls
    if stalls:
        assert excesses[40] == pytest.approx((factor - 1) * pace)


class _FakeLoss:
    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready


def _scripted_step(monkeypatch, calls):
    """A :class:`TrainStep` over a step that does nothing, driven through
    ``calls``: ``(time of the call, whether it finds the step before still
    running)``. Returns what the three step counters moved by."""
    clock = iter([t for t, _ in calls])
    monkeypatch.setattr(strategy_mod, "time", types.SimpleNamespace(monotonic=lambda: next(clock)))
    finds_running = iter([running for _, running in calls[1:]] + [False])
    step = strategy_mod.TrainStep(lambda state, batch: (state, {"loss": _FakeLoss(not next(finds_running))}))
    names = ("train_steps_dispatched_total", "train_step_stalls_total", "train_step_stall_seconds_total")
    before = [_value(n) for n in names]
    for _ in calls:
        step(None, None)
    return [_value(n) - b for n, b in zip(names, before)]


@pytest.mark.parametrize("running,stalls,seconds", [(True, 1, 0.9), (False, 0, 0.0)])
def test_a_long_interval_is_a_stall_only_when_the_device_was_late(monkeypatch, running, stalls, seconds):
    """Steady calls, then one a second late. The step before still running:
    the host waited for the device (a stall, by its excess over the median).
    Its result already there: the loop did not come back, the queue ran dry,
    and that is not the device's: nothing is booked."""
    calls = [(0.0, False)] + [(t, True) for t in (0.1, 0.2, 0.3, 0.4)] + [(1.4, running), (1.5, True)]
    assert _scripted_step(monkeypatch, calls) == [7, stalls, pytest.approx(seconds)]


def test_intervals_round_a_fence_stay_out_of_the_median(monkeypatch):
    """The benchmark's shape: a first call that compiles for a minute, fenced
    check steps, then windows that open with a fence and keep two steps in
    flight. After a fence the first call finds its predecessor done and the
    second comes at the host's pace, a few milliseconds later; only from the
    third do intervals follow the device. None of the host's intervals may
    pull the median down (the 0.9 s steps would then read as stalls), nor the
    minute of compilation pull it up: the one late step is still found."""
    calls, t = [(0.0, False), (60.0, False), (61.0, False)], 61.0  # compile, then two fenced check steps
    for late in (None, None, 6):
        t += 1.0  # a fence
        for i in range(10):
            t += (0.005 if i == 1 else 0.9) + (2.1 if i == late else 0.0)
            calls.append((t, i > 0))
    steps, stalls, seconds = _scripted_step(monkeypatch, calls)
    assert (steps, stalls) == (len(calls), 1)
    assert seconds == pytest.approx(2.1)


@pytest.mark.parametrize("donate", [True, False])
def test_the_step_callable_lowers_like_the_jitted_function(donate):
    strategy, state, step, batch = _linear_job(donate=donate)
    bare = strategy._jit_train_step(_linear_loss, optax.sgd(0.1), False, False, donate)
    def donated(fn):  # a donated argument is marked as a donor or already aliased to its output
        text = fn.lower(state, batch).as_text()
        return text.count("jax.buffer_donor") + text.count("tf.aliasing_output")

    donors = donated(step)
    assert donors == donated(bare)
    assert (donors > 0) == donate
    assert step.trace(state, batch).jaxpr is not None  # every other attribute passes through too
    state, metrics = step(state, batch)
    assert int(metrics["step"]) == 1


def _op_names(compiled_text):
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def test_the_compiled_step_holds_both_scopes():
    _, state, step, batch = _linear_job()
    names = _op_names(step.lower(state, batch).compile().as_text())
    phases = {_program.phase_of(n) for n in names}
    assert any("/tos.loss_and_grad/" in n for n in names) and any("/tos.optimizer/" in n for n in names)
    assert {"fwd", "bwd", "opt"} <= phases and "recompute" not in phases


def test_a_checkpointed_loss_yields_all_four_phases():
    def block(x, w):
        return jnp.tanh(x @ w)

    def loss_fn(params, batch):
        x = batch["x"]
        for w in params["ws"]:
            x = jax.checkpoint(block)(x, w)
        return jnp.mean(x ** 2)

    strategy = SyncDataParallel(_mesh())
    optimizer = optax.adamw(1e-3)
    state = strategy.create_state(lambda: {"ws": [jnp.full((16, 16), 0.1)] * 3}, optimizer)
    step = strategy.compile_train_step(loss_fn, optimizer)
    batch = strategy.shard_batch({"x": np.ones((8, 16), np.float32)})
    names = _op_names(step.lower(state, batch).compile().as_text())
    assert {"fwd", "recompute", "bwd", "opt"} <= {_program.phase_of(n) for n in names}


def test_compile_listener_tells_a_load_from_a_compile():
    """JAX reports a program loaded from the cache as a retrieval and then a
    "backend compile" on the same thread; a compiled one as the latter alone."""
    names = ("compile_cache_load_seconds", "compile_cache_hits", "compile_backend_seconds", "compile_cache_misses")
    before = [_value(n) for n in names]
    util._note_compile_event(util._CACHE_LOAD_EVENT, 20.0)
    util._note_compile_event(util._BACKEND_COMPILE_EVENT, 20.5, fun_name="jit(tos_train_step)")
    util._note_compile_event("/jax/core/compile/jaxpr_trace_duration", 9.0)
    util._note_compile_event(util._BACKEND_COMPILE_EVENT, 3.0)
    assert [_value(n) - b for n, b in zip(names, before)] == [20.0, 1, 3.0, 1]


def test_placing_the_cache_listens_once_and_reads_zero_before_any_compile():
    code = (
        "import json\n"
        "from tensorflowonspark_tpu import obs, util\n"
        "util.place_compile_cache(); util.place_compile_cache()\n"
        "from jax._src import monitoring\n"
        "n = sum(1 for f in monitoring.get_event_duration_listeners() if f is util._note_compile_event)\n"
        "print(json.dumps([n, obs.snapshot()['gauges']['compile_cache_load_seconds']['value']]))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120, cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "[1, 0.0]"


def test_a_process_off_jax_with_the_cache_placed_outside_stays_off_jax(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: there is nothing to set in code, so
    a process that has not imported jax (the thin serving client) is not made
    to for the listener's sake; one that has gets the listener."""
    code = (
        "import sys\n"
        "from tensorflowonspark_tpu import util\n"
        "assert util.place_compile_cache() == sys.argv[1] and 'jax' not in sys.modules\n"
        "import jax\n"
        "from jax._src import monitoring\n"
        "assert util._note_compile_event not in monitoring.get_event_duration_listeners()\n"
        "util.place_compile_cache()\n"
        "assert util._note_compile_event in monitoring.get_event_duration_listeners()\n"
    )
    out = subprocess.run([sys.executable, "-c", code, str(tmp_path)], capture_output=True, text=True, timeout=120,
                         cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert out.returncode == 0, out.stderr


# -- a start's compile work, stage by stage (PR 36) ---------------------------

OLD_GAUGES = ("compile_cache_load_seconds", "compile_cache_hits", "compile_backend_seconds", "compile_cache_misses")
STAGE_GAUGES = ("compile_trace_seconds", "compile_lower_seconds", "compile_cache_lookup_seconds")
STEP_GAUGES = ("train_step_traces", "train_step_trace_seconds", "train_step_lower_seconds",
               "train_step_cache_lookup_seconds", "train_step_cache_load_seconds",
               "train_step_backend_compile_seconds")
TRACE, LOWER = util._TRACE_EVENT, util._LOWER_EVENT
LOAD, BACKEND = util._CACHE_LOAD_EVENT, util._BACKEND_COMPILE_EVENT


def _feed(monkeypatch, script):
    """Hand the listener ``(time it ends, event, seconds, program)`` in turn,
    on a scripted clock; returns what every compile gauge moved by."""
    names = OLD_GAUGES + STAGE_GAUGES + STEP_GAUGES
    before = {n: _value(n) for n in names}
    at = iter([t for t, _, _, _ in script if t is not None])
    monkeypatch.setattr(util, "time", types.SimpleNamespace(monotonic=lambda: next(at)))
    monkeypatch.setattr(util, "_compile_thread", util.threading.local())
    for _, event, secs, program in script:
        if program is None:  # the retrieval event carries no name, and reads no clock
            util._note_compile_event(event, secs)
        else:
            util._note_compile_event(event, secs, fun_name=program)
    return {n: _value(n) - before[n] for n in names}


def _retrieval(secs):
    return (None, LOAD, secs, None)


def test_compile_listener_books_every_stage_of_every_program(monkeypatch):
    """A loaded train step, another loaded program and a compiled one, as JAX
    reports them: each stage when it ends, what ran inside the step's trace
    (a jnp function's own trace, an operation loaded and run on constants)
    before the trace that holds it."""
    script = [
        (8.0, TRACE, 1.0, "add"),  # 7 → 8, inside the step's trace (6 → 10)
        (9.0, TRACE, 0.25, "iota"), (9.125, LOWER, 0.125, "jit(iota)"),
        _retrieval(0.25), (9.5, BACKEND, 0.375, "jit(iota)"),
        (10.0, TRACE, 4.0, "tos_train_step"),
        (13.0, LOWER, 3.0, "jit(tos_train_step)"),
        _retrieval(5.0), (20.0, BACKEND, 6.5, "jit(tos_train_step)"),
        (21.0, TRACE, 0.5, "leaf_norms"), (22.0, LOWER, 0.25, "jit(leaf_norms)"),
        _retrieval(0.5), (23.0, BACKEND, 0.75, "jit(leaf_norms)"),
        (30.0, TRACE, 1.0, "f"), (31.0, LOWER, 0.5, "jit(f)"), (40.0, BACKEND, 8.0, "jit(f)"),
    ]
    moved = _feed(monkeypatch, script)
    assert moved == {
        "compile_cache_load_seconds": 5.75, "compile_cache_hits": 3, "compile_backend_seconds": 8.0,
        "compile_cache_misses": 1,
        # the step's 4 s hold 1 + 0.25 + 0.125 + 0.375 s of other stages: every second once
        "compile_trace_seconds": 1.0 + 0.25 + (4.0 - 1.75) + 0.5 + 1.0,
        "compile_lower_seconds": 0.125 + 3.0 + 0.25 + 0.5,
        "compile_cache_lookup_seconds": 0.125 + 1.5 + 0.25,
        # the step's own, whole, as JAX reports them
        "train_step_traces": 1, "train_step_trace_seconds": 4.0, "train_step_lower_seconds": 3.0,
        "train_step_cache_lookup_seconds": 1.5, "train_step_cache_load_seconds": 5.0,
        "train_step_backend_compile_seconds": 0.0,
    }


@pytest.mark.parametrize("program,is_step", [
    ("jit(tos_train_step)", True), ("jit_tos_train_step", True), ("tos_train_step", True),
    ("jit(tos_train_step_loop)", False), ("pmap(tos_train_step)", False),
])
def test_the_train_step_is_told_by_its_programs_name(monkeypatch, program, is_step):
    """A cold start: the step compiled, under each form of its name."""
    moved = _feed(monkeypatch, [(5.0, LOWER, 2.0, program), (100.0, BACKEND, 90.0, program)])
    assert (moved["compile_lower_seconds"], moved["compile_backend_seconds"], moved["compile_cache_misses"]) == (2.0, 90.0, 1)
    step = (moved["train_step_traces"], moved["train_step_lower_seconds"], moved["train_step_backend_compile_seconds"])
    assert step == ((1, 2.0, 90.0) if is_step else (0, 0.0, 0.0))


@pytest.fixture
def listening():
    util._listen_to_compiles(jax)  # once in the process, whoever asks


def test_lowering_the_step_again_on_seen_arguments_is_no_new_program(listening):
    """``step.lower`` after a call (the benchmark's memory account): JAX
    reports a trace, its cache's hit, and no lowering; the count stays."""
    _, state, step, batch = _linear_job(donate=False)
    state, metrics = step(state, batch)
    jax.block_until_ready(metrics)
    traces, seconds = _value("train_step_traces"), _value("train_step_trace_seconds")
    step.lower(state, batch)
    assert _value("train_step_traces") == traces
    assert seconds < _value("train_step_trace_seconds") < seconds + 0.05


def test_three_calls_trace_the_step_once_and_new_avals_trace_it_again(listening):
    """A real step on the CPU: call 1 traces, lowers and compiles; calls 2 and
    3 dispatch; a batch of another size is a second program. The first
    call's gauge holds call 1 alone."""
    _, state, step, batch = _linear_job(donate=False)
    names = STEP_GAUGES + ("compile_trace_seconds", "compile_lower_seconds")
    before = {n: _value(n) for n in names}
    after = []
    for _ in range(3):
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
        after.append(({n: _value(n) - before[n] for n in names}, _value("train_step_first_call_seconds")))
    moved, first_call = after[0]
    assert after[1] == after[2] == after[0]
    assert moved["train_step_traces"] == 1
    assert moved["train_step_trace_seconds"] > 0 and moved["train_step_lower_seconds"] > 0
    # nothing of the CPU's is loaded (no cache): the step was compiled
    assert moved["train_step_backend_compile_seconds"] > 0 and moved["train_step_cache_load_seconds"] == 0
    assert first_call >= sum(moved[n] for n in STEP_GAUGES[1:])
    # all programs' trace seconds hold the step's once, not the jnp functions' inside it twice
    assert moved["train_step_trace_seconds"] <= moved["compile_trace_seconds"] < 2 * moved["train_step_trace_seconds"]
    wider = SyncDataParallel(_mesh()).shard_batch(
        {"x": np.ones((16, 4), np.float32), "y": np.ones((16, 1), np.float32)})
    state, metrics = step(state, wider)
    jax.block_until_ready(metrics)
    assert _value("train_step_traces") - before["train_step_traces"] == 2
    assert _value("train_step_first_call_seconds") == first_call


def _compile_spans(shard):
    records, _ = flight.read_shard(shard)
    spans = [r for r in records if r.get("kind") == "span"]
    return [r for r in spans if r["name"].startswith("compile_")], [r for r in spans if r["name"] == "step_dispatch"]


def test_compile_stages_are_spans_under_the_call_that_caused_them(listening, tmp_path, monkeypatch):
    _, state, step, batch = _linear_job()
    tracing.reset()
    try:
        monkeypatch.setenv(flight.TRACE_DIR_ENV, str(tmp_path))
        tracing.mint(proc="unit")
        t0 = util.time.time()
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
        t1 = util.time.time()
        # a stage under the floor (a jnp function's trace inside the step's) leaves nothing
        util._note_compile_span(TRACE, t1, t1 + util._COMPILE_SPAN_FLOOR_S / 2, fun_name="add")
        shard = flight.current().shard_dir
    finally:
        tracing.reset()
    stages, (dispatch,) = _compile_spans(shard)
    assert {"compile_trace", "compile_lower", "compile_backend"} == {r["name"] for r in stages}
    own = {r["name"]: r for r in stages if r["attrs"]["program"] in util._TRAIN_STEP_PROGRAMS}
    assert set(own) == {"compile_trace", "compile_lower", "compile_backend"}
    for record in own.values():
        assert record["parent"] == dispatch["span"] and record["trace"] == dispatch["trace"]
        assert t0 <= record["ts"] and record["ts"] + record["dur_s"] <= t1 and record["dur_s"] > 0
    assert own["compile_trace"]["ts"] < own["compile_lower"]["ts"] < own["compile_backend"]["ts"]
    assert all(r["attrs"]["program"] != "add" for r in stages)


def test_compile_stages_write_nothing_without_a_flight_shard(tmp_path, monkeypatch):
    """``TOS_TRACE_DIR`` names a directory but no shard is open (nobody
    minted a trace): the listener must not be the one that opens it."""
    tracing.reset()
    monkeypatch.setenv(flight.TRACE_DIR_ENV, str(tmp_path))
    try:
        for event in (TRACE, LOWER, BACKEND):
            util._note_compile_span(event, 100.0, 101.0, fun_name="jit(tos_train_step)")
        assert not flight.is_open() and os.listdir(str(tmp_path)) == []
    finally:
        tracing.reset()


def test_disabled_collection_books_no_compile_stage(listening, monkeypatch):
    """``TOS_OBS=0``: the listener's gauges stay, the step callable is the
    bare jitted function and keeps no first call."""
    names = OLD_GAUGES + STAGE_GAUGES + STEP_GAUGES + ("train_step_first_call_seconds",)
    obs.set_enabled(False)
    try:
        _, state, step, batch = _linear_job()
        before = {n: _value(n) for n in names}
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
        fed = _feed(monkeypatch, [(10.0, TRACE, 4.0, "tos_train_step"), _retrieval(5.0),
                                  (20.0, BACKEND, 6.5, "jit(tos_train_step)")])
    finally:
        obs.set_enabled(True)
    assert int(state.step) == 1 and not any(fed.values())
    assert before == {n: _value(n) for n in names}


def test_placing_the_cache_twice_registers_each_listener_once():
    code = (
        "import json\n"
        "from tensorflowonspark_tpu import obs, util\n"
        "util.place_compile_cache(); util.place_compile_cache()\n"
        "from jax._src import monitoring\n"
        "n = [sum(1 for f in monitoring.get_event_duration_listeners() if f is util._note_compile_event),\n"
        "     sum(1 for f in monitoring.get_event_time_span_listeners() if f is util._note_compile_span)]\n"
        "gauges = obs.snapshot()['gauges']\n"
        "print(json.dumps([n, sorted(k for k, v in gauges.items() if v['value'] == 0.0)]))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120, cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    registered, zero = json.loads(out.stdout.strip().splitlines()[-1])
    # every gauge is there at 0 before any program: a reader tells "no seconds" from "does not count"
    assert registered == [1, 1] and set(OLD_GAUGES + STAGE_GAUGES + STEP_GAUGES) <= set(zero)


def _text_pipeline(tmp_path):
    from tensorflowonspark_tpu import tfrecord
    from tensorflowonspark_tpu.data import TextPipeline, Tokenizer

    path = str(tmp_path / "part-00000")
    with tfrecord.TFRecordWriter(path) as w:
        for i in range(200):
            w.write(" ".join(["spark", "text", "plane", str(i)] * (1 + i % 5)).encode())
    return TextPipeline([path], Tokenizer(kind="word", vocab_size=128), seq_len=48, batch_size=4, seed=7, epochs=None)


def _image_pipeline(tmp_path):
    from tensorflowonspark_tpu import tfrecord
    from tensorflowonspark_tpu.data import ImagePipeline

    path = str(tmp_path / "part-00000")
    with tfrecord.TFRecordWriter(path) as w:
        for i in range(200):
            w.write(str(i).encode())
    return ImagePipeline([path], lambda rec: (np.full((4, 4, 1), int(rec) % 251, np.uint8), int(rec)),
                         batch_size=8, seed=3, epochs=None)


@pytest.mark.parametrize("make", [_text_pipeline, _image_pipeline], ids=["text", "image"])
def test_the_first_batch_is_timed_once_an_iterator(make, tmp_path):
    """From the iterator's start (the first ``next``) to its first batch;
    later batches leave the gauge alone, a new iterator sets it anew."""
    obs.gauge("data_first_batch_seconds").set(-1.0)
    stream = iter(make(tmp_path))
    try:
        t0 = util.time.monotonic()
        next(stream)
        took = util.time.monotonic() - t0
        first = _value("data_first_batch_seconds")
        assert 0 < first <= took
        for _ in range(3):
            next(stream)
        assert _value("data_first_batch_seconds") == first
    finally:
        stream.close()
    again = iter(make(tmp_path))
    try:
        obs.gauge("data_first_batch_seconds").set(-1.0)
        next(again)
        assert _value("data_first_batch_seconds") > 0
    finally:
        again.close()
