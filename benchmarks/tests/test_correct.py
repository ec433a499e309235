"""What decides ``correct``, at sizes a test run can hold.

* the control — the reference in float8 — comes out as not correct under the
  cells' own limits;
* a run whose step returns its state unchanged comes out as not correct,
  driven through everything of a run but the harness's look for a chip;
* ``run.py`` gives no result where there is no TPU, and rehearses end to end.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks import check, child, run


def _spec(workload, tmp_path, seconds=0.5):
    _, cell, config, traffic = run.resolve(workload, rehearse=True)
    return {
        "workload": workload, "chips": 1, "seed": 2147483659, "seconds": seconds, "trace": False,
        "rehearse": True, "config": config, "traffic": traffic, "scratch": str(tmp_path),
    }


def _ctx():
    return types.SimpleNamespace(
        initialize_distributed=lambda: None, num_processes=1, num_workers=1, executor_id=0)


def _run(workload, tmp_path, broken):
    import importlib

    import jax
    import jax.numpy as jnp

    spec = _spec(workload, tmp_path)
    family = importlib.import_module("benchmarks.families." + spec["config"]["family"])

    def build(spec, ctx, parts):
        job = family.build(spec, ctx, parts)
        if broken:
            real = job.step

            def unchanged(state, batch):
                # the step runs, its new state is dropped
                _, metrics = real(jax.tree.map(jnp.copy, state), batch)
                return state, metrics

            job.step = _Callable(unchanged, real)
        return job

    return child.run_job(spec, _ctx(), 0.0, build=build, out=lambda line: None)


class _Callable:
    def __init__(self, fn, real):
        self.fn, self.lower = fn, real.lower

    def __call__(self, *args):
        return self.fn(*args)


def test_sound_lm_run_passes_and_broken_step_fails(tmp_path):
    sound = _run("lm1024.packed4k", tmp_path / "sound", broken=False)
    assert sound["check_ok"] and not sound["correct"]  # a rehearsal never reports correct
    assert sound["check"]["change_gap"] < 0.01
    broken = _run("lm1024.packed4k", tmp_path / "broken", broken=True)
    assert not broken["check_ok"]
    assert broken["check"]["change_gap"] > 0.99 and broken["check"]["grad_gap"] > 0.99
    assert broken["check"]["dir_gap"] > 0.99


def test_broken_image_step_fails(tmp_path):
    broken = _run("resnet50.warm", tmp_path / "broken", broken=True)
    assert not broken["check_ok"]
    assert broken["check"]["change_gap"] > 0.99 and broken["check"]["grad_gap"] > 0.99


@pytest.mark.parametrize("workload", ["lm1024.packed4k", "resnet50.warm"])
def test_float8_control_is_not_correct(workload, tmp_path):
    import importlib

    import jax

    spec = _spec(workload, tmp_path)
    family = spec["config"]["family"]
    reference = importlib.import_module("benchmarks.reference." + family)
    rng = np.random.default_rng(5)
    if family == "lm":
        rows, seq = 2, spec["traffic"]["seq_len"] + 1
        seg = np.repeat(np.array([[1] * 100 + [2] * 120 + [0] * (seq - 220)]), rows, axis=0)
        pos = np.concatenate([np.arange(100), np.arange(120), np.zeros(seq - 220, int)])
        batch = {"tokens": rng.integers(3, spec["config"]["vocab_size"], (rows, seq)).astype(np.int32),
                 "segment_ids": seg.astype(np.int32), "positions": np.repeat(pos[None], rows, axis=0).astype(np.int32)}
    else:
        size = spec["config"]["image_size"]
        batch = {"image": rng.integers(0, 256, (16, size, size, 3)).astype(np.uint8),
                 "label": rng.integers(0, 1000, 16).astype(np.int32)}
    key, devices = jax.random.PRNGKey(7), jax.devices()[:1]
    want = reference.follow(spec["config"], key, [batch] * 2, devices)
    control = reference.follow(spec["config"], key, [batch] * 2, devices, quant="fp8")
    read = check.readings(control, want)
    limits = check.load_limits(workload)
    correct, _ = check.judge(read, limits)
    assert not correct, read
    assert read["dir_gap"] > limits["dir_gap"], read  # the number that separates on the chip
    same, _ = check.judge(check.readings(want, want), check.load_limits(workload))
    assert same


def test_reference_programs_do_not_depend_on_the_seed(tmp_path):
    """A second seed finds every program of both references in the compile
    cache: a key closed over becomes a constant of the program, and every run
    would compile the reference again (on the chip, 100 s for 9)."""
    script = """
import sys, jax, numpy as np
sys.path.insert(0, {root!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from benchmarks import run
from benchmarks.reference import image, lm
events = []
jax.monitoring.register_event_listener(lambda e, **kw: events.append(e))
rng = np.random.default_rng(0)
for ref, cell in ((image, "resnet50.warm"), (lm, "lm1024.packed4k")):
    _, _, cfg, traffic = run.resolve(cell, rehearse=True)
    if ref is image:
        batch = dict(image=rng.integers(0, 256, (8, 32, 32, 3)).astype(np.uint8),
                     label=rng.integers(0, 1000, 8).astype(np.int32))
    else:
        seq = traffic["seq_len"] + 1
        batch = dict(tokens=rng.integers(3, 500, (2, seq)).astype(np.int32), segment_ids=np.ones((2, seq), np.int32),
                     positions=np.tile(np.arange(seq), (2, 1)).astype(np.int32))
    for seed in (1, 2):
        del events[:]
        ref.follow(cfg, jax.random.PRNGKey(seed), [batch], jax.devices()[:1])
        print("MISSES", cell, seed, sum("cache_misses" in e for e in events))
""".format(root=run.ROOT)
    done = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert done.returncode == 0, done.stderr[-2000:]
    misses = {tuple(line.split()[1:3]): int(line.split()[3]) for line in done.stdout.splitlines()
              if line.startswith("MISSES")}
    assert misses[("resnet50.warm", "1")] > 0 and misses[("lm1024.packed4k", "1")] > 0
    assert misses[("resnet50.warm", "2")] == 0 and misses[("lm1024.packed4k", "2")] == 0


def _run_py(*args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), *args], capture_output=True, text=True,
        env=dict(os.environ, **(env or {})), timeout=600)


def test_no_tpu_no_result():
    done = _run_py("--workload", "lm1024.packed4k", "--seed", "1", "--seconds", "1", env={"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert not done.stdout.strip().endswith("}")


@pytest.mark.parametrize("workload", ["lm1024.packed4k", "resnet50.warm"])
def test_rehearsal_end_to_end(workload):
    done = _run_py("--workload", workload, "--seed", "3000000011", "--seconds", "1", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["metrics"] and all(name.startswith("cpu.") for name in line["metrics"])
    assert line["device"]["busy_s"] > 0 and line["breakdown"]["device_ops"]
