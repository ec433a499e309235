"""Small host-side utilities shared by driver and executors.

Capability-parity with /root/reference/tensorflowonspark/util.py (IP discovery,
PATH search, executor-id persistence, single-node env setup) but adapted for the
jax/TPU runtime: ``single_node_env`` prepares a jax process instead of a TF one,
and the executor-id file also records the local IPC manager address so later
Spark tasks landing on the same executor can reconnect to the running jax
process (reference: util.py:77-86 + TFSparkNode.py:97-123).
"""

import collections
import errno
import functools
import json
import logging
import multiprocessing
import os
import socket
import sys
import threading
import time

from tensorflowonspark_tpu import durable, obs
from tensorflowonspark_tpu.obs import flight, tracing

logger = logging.getLogger(__name__)

_mp_spawn = multiprocessing.get_context("spawn")

#: log format carrying process/thread names — the runtime spans a driver,
#: N executor processes and N jax child processes, so bare messages are
#: un-attributable (reference tensorflowonspark/__init__.py:3)
LOG_FORMAT = "%(asctime)s %(levelname)s (%(processName)s %(threadName)s) %(name)s: %(message)s"


def setup_logging(level=logging.INFO):
    """Configure root logging for an APPLICATION entry point (examples,
    the jax child process). Libraries must never do this at import
    time — importing :mod:`tensorflowonspark_tpu` leaves the root logger's
    handlers untouched so embedding applications keep control of their own
    logging (enforced by the ``import-hygiene`` rule of ``python -m tosa``
    and a regression test). No-op if the root logger is already configured."""
    logging.basicConfig(level=level, format=LOG_FORMAT)


def _spawn_trampoline(blob):
    import cloudpickle

    cloudpickle.loads(blob)()


def spawn_process(fn, name=None):
    """A ``multiprocessing.Process`` running ``fn()`` in a **spawned** child.

    Spawn (not fork) everywhere: executors, IPC servers, and jax children are
    all started from processes that may carry threads (pytest, jax's own
    thread pools, queue feeders), and forking a threaded process deadlocks —
    python 3.12 warns about exactly this. ``fn`` may be any cloudpickle-able
    zero-arg callable (closures included); a spawned child only needs the
    module-level trampoline to be importable.
    """
    import cloudpickle

    return _mp_spawn.Process(target=_spawn_trampoline, args=(cloudpickle.dumps(fn),), name=name)

# Name of the per-executor state file written into the executor's CWD.
EXECUTOR_STATE_FILE = "tos_tpu_executor.json"


def get_ip_address():
    """Best-effort routable IP address of this host.

    Uses the UDP-connect trick (no packet is actually sent, so it works in
    zero-egress environments), falling back to hostname resolution and finally
    loopback. Reference: util.py:52.
    """
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("8.8.8.8", 53))
            return s.getsockname()[0]
    except OSError:
        pass
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:
        return "127.0.0.1"


def find_in_path(path, file_name):
    """Find a file within a ':'-separated search path (reference util.py:68)."""
    for p in path.split(os.pathsep):
        candidate = os.path.join(p, file_name)
        if os.path.exists(candidate) and os.path.isfile(candidate):
            return candidate
    return False


def write_executor_state(state, cwd=None):
    """Persist per-executor bootstrap state (executor id, IPC manager address,
    authkey) to a file in the executor's working directory.

    The reference persisted just the executor id (util.py:77-82); we persist the
    whole reconnect record because feeding tasks scheduled later onto this
    executor must find the already-running jax process's IPC manager.
    ``authkey`` bytes are hex-encoded.
    """
    record = dict(state)
    if isinstance(record.get("authkey"), bytes):
        record["authkey"] = record["authkey"].hex()
        record["authkey_hex"] = True
    path = os.path.join(cwd or os.getcwd(), EXECUTOR_STATE_FILE)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    # reconnect-after-crash reads this record; a torn or vanished file
    # strands later tasks without the running jax child's IPC address
    durable.fsync_dir(os.path.dirname(path))
    return path


def read_executor_state(cwd=None):
    """Read the record written by :func:`write_executor_state`, or None."""
    path = os.path.join(cwd or os.getcwd(), EXECUTOR_STATE_FILE)
    try:
        with open(path) as f:
            record = json.load(f)
    except OSError as e:
        if e.errno in (errno.ENOENT,):
            return None
        raise
    if record.pop("authkey_hex", False):
        record["authkey"] = bytes.fromhex(record["authkey"])
    return record


def force_platform(platform, num_cpu_devices=None):
    """Force the jax platform for THIS process: the env var for a jax not
    yet imported (and for children), the config API for one that is — pytest
    and other hosts have jax imported before they get here, and by then the
    env var has been read. Must run before the first jax backend use.
    ``num_cpu_devices`` forces that many virtual CPU devices (test worlds).
    """
    os.environ["JAX_PLATFORMS"] = platform
    if num_cpu_devices and platform == "cpu":
        import re

        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            "",
            os.environ.get("XLA_FLAGS", ""),
        )
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count={}".format(int(num_cpu_devices))
        ).strip()
    import jax

    jax.config.update("jax_platforms", platform)


#: the variable JAX itself reads for its persistent compilation cache; the
#: driver forwards it on the env lane when set (``TFCluster.run``)
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def place_compile_cache():
    """Give this process a persistent compilation cache; returns its path
    (None when it gets none).

    Called at the top of every process that compiles (the jax child, a
    TFParallel instance, the serving CLI).
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is set in code. Otherwise the cache goes to ``.jax_cache`` beside
    the package — a fixed path, because the path is what lets the next
    process (an elastic relaunch, the next run) find the entries again; a
    temporary directory, a pid or a time in it would never hit. A process
    pinned to the CPU platform (the test worlds) gets none unless the
    variable names one: XLA:CPU logs a multi-kilobyte machine-feature
    complaint for every executable it loads back.

    Where jax is imported by then, the process also starts keeping what JAX
    reports of each stage of its compilations — tracing, lowering, the
    cache's key, the load or the compilation — for all programs
    (``compile_*`` gauges) and for the train step by its name
    (``train_step_*`` gauges), and writes the stages as ``compile_*`` spans
    where a flight shard is open.
    """
    placed = os.environ.get(COMPILE_CACHE_ENV)
    if placed:
        # nothing to set: a process that has not imported jax (the thin
        # serving client) is not made to, and has no compilations to report
        if "jax" in sys.modules:
            _listen_to_compiles(sys.modules["jax"])
        return placed
    import jax

    _listen_to_compiles(jax)
    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return None
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the train step's program as each stage's event names it (the function's
#: name is fixed in ``SyncDataParallel._jit_train_step`` for this)
_TRAIN_STEP_PROGRAMS = ("tos_train_step", "jit(tos_train_step)", "jit_tos_train_step")
#: a compile stage shorter than this leaves no span in the flight shard: the
#: step's trace holds thousands of jnp functions' own, 0.1 ms each
_COMPILE_SPAN_FLOOR_S = 1e-3
#: a thread remembers this many ended stages: every stage that ends inside
#: another is forgotten with it, so what stays is one entry a program
_STAGES_KEPT = 1 << 16
_compile_thread = threading.local()
_listening = False


def _listen_to_compiles(jax):
    """Register, once in a process, the two listeners that keep what JAX
    reports of each stage of its compilations: durations in gauges (gauges,
    because set-up is over before anybody takes a window's delta of
    counters), and each stage's start and end as a span of the flight shard."""
    global _listening
    if _listening:
        return
    _listening = True
    _compile_gauges()  # a process that never loads reads 0, not nothing
    jax.monitoring.register_event_duration_secs_listener(_note_compile_event)
    jax.monitoring.register_event_time_span_listener(_note_compile_span)


@functools.cache
def _compile_gauges():
    """``(all programs', the train step's)``: each a dict of gauges by stage,
    created at 0 at the first call."""
    every = {
        "trace": obs.gauge(
            "compile_trace_seconds",
            help="seconds tracing programs to jaxprs, a trace inside another "
            "(an inner jit, an operation run while tracing) counted once",
        ),
        "lower": obs.gauge(
            "compile_lower_seconds",
            help="seconds lowering jaxprs to StableHLO modules, less what other "
            "stages took inside them",
        ),
        "lookup": obs.gauge(
            "compile_cache_lookup_seconds",
            help="seconds loaded programs spent beside their load: the cache "
            "key (the module canonicalised and hashed) and the options",
        ),
        "load": obs.gauge(
            "compile_cache_load_seconds",
            help="seconds spent loading executables from the persistent compile cache",
        ),
        "hits": obs.gauge(
            "compile_cache_hits", help="programs loaded from the persistent compile cache"
        ),
        "backend": obs.gauge(
            "compile_backend_seconds",
            help="seconds the backend spent compiling programs it did not load",
        ),
        "misses": obs.gauge(
            "compile_cache_misses",
            help="programs the backend compiled, not loaded from the cache",
        ),
    }
    step = {
        "traces": obs.gauge(
            "train_step_traces",
            help="programs built of the train step in this process: 1, and one "
            "more for every call whose arguments' types, shardings or layouts "
            "were new (its lowerings: JAX reports a trace for a cached one too)",
        ),
        "trace": obs.gauge(
            "train_step_trace_seconds", help="seconds tracing the train step, as JAX reports them"
        ),
        "lower": obs.gauge(
            "train_step_lower_seconds", help="seconds lowering the train step to a StableHLO module"
        ),
        "lookup": obs.gauge(
            "train_step_cache_lookup_seconds",
            help="seconds a loaded train step spent beside its load (the cache key)",
        ),
        "load": obs.gauge(
            "train_step_cache_load_seconds",
            help="seconds loading the train step from the persistent compile cache",
        ),
        "backend": obs.gauge(
            "train_step_backend_compile_seconds",
            help="seconds the backend spent compiling the train step (0 on a warm start)",
        ),
    }
    return every, step


def _note_compile_event(event, secs, fun_name=None, **_kw):
    """Book one stage of one program, as JAX reports it when the stage ends.

    A program is traced, lowered, then handed to the backend. The "backend
    compile" event of one that is loaded from the persistent cache holds the
    retrieval, which JAX reports first, on the same thread and without the
    program's name, and before it the cache's key: the module stripped,
    serialised and hashed. A compiled program reports the backend compile
    alone. The train step's stages are booked a second time by its name.

    Stages lie inside each other (a jnp function is traced inside the step's
    trace, an operation on constants is compiled and run inside it), so for
    all programs a trace or a lowering counts less what ended inside it: the
    stages' gauges add up to host time. What ended inside is what this thread
    noted after the stage began, by this listener's own clock.
    """
    if event == _CACHE_LOAD_EVENT:
        every, _ = _compile_gauges()
        _compile_thread.loaded = secs
        every["load"].inc(secs)
        every["hits"].inc()
        return
    if event not in (_TRACE_EVENT, _LOWER_EVENT, _BACKEND_COMPILE_EVENT):
        return
    every, step = _compile_gauges()
    step = step if fun_name in _TRAIN_STEP_PROGRAMS else None
    now = time.monotonic()
    ended = getattr(_compile_thread, "ended", None)
    if ended is None:
        ended = _compile_thread.ended = collections.deque(maxlen=_STAGES_KEPT)
    inside = 0.0
    while ended and ended[-1][0] > now - secs:
        inside += ended.pop()[1]
    ended.append((now, secs))
    if event == _BACKEND_COMPILE_EVENT:
        loaded = _compile_thread.__dict__.pop("loaded", None)
        if loaded is None:
            every["backend"].inc(secs)
            every["misses"].inc()
            if step:
                step["backend"].inc(secs)
        else:
            every["lookup"].inc(secs - loaded)
            if step:
                step["load"].inc(loaded)
                step["lookup"].inc(secs - loaded)
        return
    stage = "trace" if event == _TRACE_EVENT else "lower"
    every[stage].inc(max(secs - inside, 0.0))
    if step:
        step[stage].inc(secs)
        if stage == "lower":
            step["traces"].inc()


def _note_compile_span(event, start, end, fun_name=None, **_kw):
    """The same stages with JAX's own start and end (``time.time()``, the
    flight shard's clock), as spans under whatever span the compiling thread
    has open: ``step_dispatch`` for the step's, ``node_main`` for most others.
    Nothing is written unless a flight shard is open (``TOS_TRACE_DIR``)."""
    if not flight.is_open() or end - start < _COMPILE_SPAN_FLOOR_S:
        return
    if event == _TRACE_EVENT:
        tracing.record_span("compile_trace", start, end - start, program=fun_name)
    elif event == _LOWER_EVENT:
        tracing.record_span("compile_lower", start, end - start, program=fun_name)
    elif event == _BACKEND_COMPILE_EVENT:
        tracing.record_span("compile_backend", start, end - start, program=fun_name)


def single_node_env(num_cpu_devices=None, platform=None):
    """Prepare the environment for a *single-node* jax process.

    The reference's version wired up the Hadoop classpath and CUDA_VISIBLE_DEVICES
    (util.py:21-49); the TPU-native analogue selects the jax platform and,
    for CPU-backed tests, a virtual device count — this must run before jax is
    imported in the process.
    """
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
    if num_cpu_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        opt = "--xla_force_host_platform_device_count={}".format(num_cpu_devices)
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (flags + " " + opt).strip()


def find_free_port(host=""):
    """Bind-and-release a TCP port; used for coordinator/profiler ports.

    The reference bound a free port for the TF grpc server
    (TFSparkNode.py:252-255); here ports are needed for the jax.distributed
    coordinator and the profiler server.
    """
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((host, 0))
        return s.getsockname()[1]
