import json
import os
import subprocess
import sys

import pytest

from tensorflowonspark_tpu import util


def test_import_configures_no_logging():
    """Importing the library must not touch the root logger (the import-time
    basicConfig this repo used to ship hijacked logging from every host
    application). Run in a fresh interpreter: this process imported the
    package long ago."""
    code = (
        "import logging\n"
        "before = list(logging.getLogger().handlers)\n"
        "level = logging.getLogger().level\n"
        "import tensorflowonspark_tpu\n"
        "import tensorflowonspark_tpu.util\n"
        "assert list(logging.getLogger().handlers) == before, 'import added handlers'\n"
        "assert logging.getLogger().level == level, 'import changed root level'\n"
        "print('clean')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr
    assert "clean" in out.stdout


def test_setup_logging_configures_root():
    # basicConfig is a no-op on an already-configured root, so check in a
    # subprocess where the root is pristine
    code = (
        "import logging\n"
        "from tensorflowonspark_tpu import util\n"
        "util.setup_logging(level=logging.DEBUG)\n"
        "root = logging.getLogger()\n"
        "assert root.level == logging.DEBUG\n"
        "assert root.handlers, 'setup_logging installed no handler'\n"
        "fmt = root.handlers[0].formatter._fmt\n"
        "assert fmt == util.LOG_FORMAT, fmt\n"
        "print('configured')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr
    assert "configured" in out.stdout


def test_ip_address_is_string():
    ip = util.get_ip_address()
    assert isinstance(ip, str) and ip.count(".") == 3


def test_find_in_path(tmp_path):
    f = tmp_path / "tool"
    f.write_text("x")
    path = os.pathsep.join(["/nonexistent", str(tmp_path)])
    assert util.find_in_path(path, "tool") == str(f)
    assert util.find_in_path(path, "missing") is False


def test_executor_state_roundtrip(tmp_path):
    state = {"executor_id": 3, "address": ["10.0.0.1", 4000], "authkey": b"\x01\x02"}
    util.write_executor_state(state, cwd=str(tmp_path))
    got = util.read_executor_state(cwd=str(tmp_path))
    assert got["executor_id"] == 3
    assert got["address"] == ["10.0.0.1", 4000]
    assert got["authkey"] == b"\x01\x02"


def test_read_executor_state_missing(tmp_path):
    assert util.read_executor_state(cwd=str(tmp_path)) is None


def test_find_free_port():
    p = util.find_free_port()
    assert 0 < p < 65536


_CACHE_PROBE = (
    "from tensorflowonspark_tpu import util\n"
    "placed = util.place_compile_cache()\n"
    "import jax, json\n"
    "print(json.dumps([placed, jax.config.jax_compilation_cache_dir]))\n"
)


def _cache_probe(**env):
    base = {k: v for k, v in os.environ.items() if k != util.COMPILE_CACHE_ENV}
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE],
        capture_output=True, text=True, timeout=120, env=dict(base, **env),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX uses it, the helper sets no other
    directory in code (the config value is exactly the variable's)."""
    placed, in_use = _cache_probe(JAX_COMPILATION_CACHE_DIR=str(tmp_path), JAX_PLATFORMS="tpu")
    assert placed == in_use == str(tmp_path)


def test_compile_cache_default_is_one_fixed_in_checkout_path():
    """Unset: <checkout>/.jax_cache, derived from the package's location —
    the same string in every process (the path is part of what makes the
    next process find the entries), never a temp dir, a pid or a time.
    No backend is initialized, so asking for the TPU platform needs none."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(util.__file__)))
    first = _cache_probe(JAX_PLATFORMS="tpu")
    second = _cache_probe(JAX_PLATFORMS="tpu,cpu")
    assert first == second == [os.path.join(root, ".jax_cache")] * 2


def test_compile_cache_not_placed_for_cpu_pinned_processes():
    """The CPU test worlds get no cache unless the variable names one."""
    assert _cache_probe(JAX_PLATFORMS="cpu") == [None, None]


@pytest.mark.parametrize("package", ["train", "parallel", "ops", "ckpt"])
def test_lazy_exports_resolve(package):
    """Every name a lazily exporting package offers is there to be had: the
    table outlives a deleted module or function in silence until someone
    asks for the name."""
    import importlib

    pkg = importlib.import_module("tensorflowonspark_tpu." + package)
    assert pkg._EXPORTS
    for name in pkg._EXPORTS:
        assert getattr(pkg, name) is not None, name
    assert dir(pkg) == sorted(pkg._EXPORTS)
