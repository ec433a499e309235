"""Small host-side utilities shared by driver and executors.

Capability-parity with /root/reference/tensorflowonspark/util.py (IP discovery,
PATH search, executor-id persistence, single-node env setup) but adapted for the
jax/TPU runtime: ``single_node_env`` prepares a jax process instead of a TF one,
and the executor-id file also records the local IPC manager address so later
Spark tasks landing on the same executor can reconnect to the running jax
process (reference: util.py:77-86 + TFSparkNode.py:97-123).
"""

import errno
import json
import logging
import multiprocessing
import os
import socket
import sys
import threading

from tensorflowonspark_tpu import durable, obs

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
    reports of its compilations and cache loads (``compile_cache_*`` and
    ``compile_backend_seconds`` gauges).
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
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_thread = threading.local()
_listening = False


def _listen_to_compiles(jax):
    """Register, once in a process, the listener that keeps what JAX reports
    of its compilations in four gauges (gauges, because set-up is over before
    anybody takes a window's delta of counters)."""
    global _listening
    if _listening:
        return
    _listening = True
    for gauge in _compile_gauges():  # a process that never loads reads 0, not nothing
        gauge.inc(0)
    jax.monitoring.register_event_duration_secs_listener(_note_compile_event)


def _compile_gauges():
    return (
        obs.gauge(
            "compile_cache_load_seconds",
            help="seconds spent loading executables from the persistent compile cache",
        ),
        obs.gauge(
            "compile_cache_hits", help="programs loaded from the persistent compile cache"
        ),
        obs.gauge(
            "compile_backend_seconds",
            help="seconds the backend spent compiling programs it did not load",
        ),
        obs.gauge(
            "compile_cache_misses",
            help="programs the backend compiled, not loaded from the cache",
        ),
    )


def _note_compile_event(event, secs, **_kw):
    """A program loaded from the persistent cache reports its retrieval and
    then, on the same thread, a "backend compile" that holds nothing else;
    one that was compiled reports the latter alone."""
    if event not in (_CACHE_LOAD_EVENT, _BACKEND_COMPILE_EVENT):
        return
    load_seconds, hits, backend_seconds, misses = _compile_gauges()
    if event == _CACHE_LOAD_EVENT:
        _compile_thread.loaded = True
        load_seconds.inc(secs)
        hits.inc()
    elif getattr(_compile_thread, "loaded", False):
        _compile_thread.loaded = False
    else:
        backend_seconds.inc(secs)
        misses.inc()


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
