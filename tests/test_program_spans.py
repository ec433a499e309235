"""The program's spans on the profiler's clock (PR 24): ``obs.span`` as a
``tos.<name>`` annotation in a ``jax.profiler`` trace, per-step spans that
leave only their counter behind, the placement / step-dispatch / stall
counters, the compile listener's gauges, and the named scopes that let a
device operation's ``op_name`` say its phase."""

import glob
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
