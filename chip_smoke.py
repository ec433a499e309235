"""Start the trainer on the chip through the entry points a user calls.

    python chip_smoke.py                            # one chip (the default)
    python chip_smoke.py --mesh dp=4 --batch_size 16      # a four-chip host
    python chip_smoke.py --cpu                      # rehearsal: prints "platform": "cpu"

The quickest proof that the system still runs on a TPU: driver →
``TFCluster.run`` on the local backend (one executor) → spawned jax child →
``examples/transformer/transformer_spark.py``'s ``main_fun`` at the repo's
full LM width (d_model 1024, 16 heads, d_ff 4096, vocab 32000, bf16, packed
4096-token rows; depth cut to 4 layers, 116M params; random weights and a
corpus made from a seed). The same cluster is run a second time from the
same driver, so the chip must have been released and the compile cache hit.

The jax child checks what only it can see — the device, the Mosaic kernels
in the compiled step, flash/plain parity on the device, losses,
compilations, the cache — and writes a report; the driver reads both
reports, checks the processes around the child, prints one JSON report and,
as the last line, ``{"ok": true, "device": {...}}``. Any failed check, node
error or timeout exits non-zero without that line. Flags other than ``--cpu``
are ``transformer_spark.py``'s own and override the defaults below.

The timings in the report say whether the program started, not how fast it
is: they are not a benchmark.
"""

import argparse
import collections
import json
import os
import shutil
import signal
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "examples", "transformer")]
OUT = os.path.join(ROOT, "chip_smoke_out")

#: the repo's full LM width, 4 per chip
FULL = (
    "--vocab_size 32000 --d_model 1024 --n_heads 16 --d_ff 4096 --n_layers 4 "
    "--seq_len 4096 --batch_size 4 --dtype bfloat16"
).split()
#: the CPU rehearsal: same program, toy widths, kernels in the interpreter
TINY = (
    "--vocab_size 512 --d_model 64 --n_heads 2 --d_ff 128 --n_layers 2 "
    "--seq_len 256 --batch_size 2 --dtype bfloat16 --attention flash_interpret"
).split()
WARMUP_STEPS, MEASURED_STEPS = 2, 6
CORPUS_SEED = 21
#: the whole script must end inside the driver's 1200 s, compilation included
DEADLINE_SECS = 1100

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_KERNELS = ("flash_fwd_seg", "flash_bwd_dkv_seg")  # one forward, one backward (dq, dk and dv)


# -- inside the jax child -----------------------------------------------------


class _Probe:
    """``main_fun``'s observer: records what the trainer did, then checks it."""

    def __init__(self, args):
        import jax

        self.args = args
        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(lambda e, **kw: self.counts.update([e]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda e, secs, **kw: self.counts.update([e])
        )
        self.cache_dir = jax.config.jax_compilation_cache_dir
        self.cache_entries_before = _count_entries(self.cache_dir)
        self.steps = []

    def _mark(self):
        return {k: self.counts[k] for k in (_HIT, _MISS, _COMPILE)}

    def built(self, mesh, state, step_fn):
        import jax

        self.mesh, self.step_fn = mesh, step_fn
        self.state_struct = jax.tree.map(_struct, state)
        self.params = sum(x.size for x in jax.tree.leaves(state.params))
        self.param_shards = _shards(state.params["layer_0"]["attn"]["q"]["kernel"])
        self.at_built, self.clock = self._mark(), time.perf_counter()

    def step(self, i, batch, metrics):
        import jax
        import numpy as np

        loss = float(metrics["loss"])  # waits for the device
        self.steps.append(
            dict(self._mark(), loss=loss, seconds=time.perf_counter() - self.clock)
        )
        if i == 1:
            self.batch_struct = jax.tree.map(_struct, batch)
            self.batch_shards = _shards(batch["tokens"])
            self.segments = np.asarray(batch["segment_ids"])[:1, :-1]
            self.bytes_in_use = [
                (d.memory_stats() or {}).get("bytes_in_use") for d in jax.local_devices()
            ]
        self.clock = time.perf_counter()

    def finish(self):
        import jax

        from tensorflowonspark_tpu import native_io, obs, tpu_info

        args, checks = self.args, []

        def check(name, ok, detail=None):
            checks.append({"check": name, "ok": bool(ok), "detail": detail})

        devices = jax.local_devices()
        platform = devices[0].platform
        on_tpu = platform == "tpu"
        check("platform", platform == args.platform, platform)
        peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
        if on_tpu:
            detected = tpu_info.detect_local_chips()
            check("tpu_info_matches_runtime", detected == len(devices),
                  {"detect_local_chips": detected, "local_devices": len(devices)})
            from jax.experimental import mesh_utils

            placed = mesh_utils.create_device_mesh(self.mesh.devices.shape, devices=devices)
            check("mesh_is_create_device_mesh",
                  [d.id for d in placed.flat] == [d.id for d in self.mesh.devices.flat])

        # the compiled step: Mosaic kernels present, per-device shapes local
        before = self._mark()
        hlo = self.step_fn.lower(self.state_struct, self.batch_struct).compile().as_text()
        recompiled = self._mark()[_MISS] - before[_MISS]
        with open(args.report_path.replace(".json", "_step_hlo.txt"), "w") as f:
            f.write(hlo)
        mosaic = _mosaic_calls(hlo)
        if on_tpu:
            sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            local_bh = (
                args.batch_size // (sizes.get("dp", 1) * sizes.get("fsdp", 1))
            ) * (args.n_heads // sizes.get("tp", 1))
            for kernel in _KERNELS:
                shapes = [c["shape"] for c in mosaic if c["kernel"] == kernel]
                check("mosaic_" + kernel,
                      len(shapes) == args.n_layers
                      and all(s == [local_bh, args.seq_len, args.d_model // args.n_heads]
                              for s in shapes),
                      {"calls": len(shapes), "shapes": shapes[:1], "expected_rows": local_bh})
            gathers = _qkv_all_gathers(hlo, args.seq_len, args.d_model // args.n_heads)
            check("no_all_gather_of_qkv", not gathers, gathers[:3])
        n_data = len({str(s["index"]) for s in self.batch_shards})
        check("batch_sharded_over_every_device",
              len(self.batch_shards) == len(devices)
              and n_data * self.batch_shards[0]["shape"][0] == args.batch_size,
              self.batch_shards)
        if "tp" in self.mesh.axis_names:
            tp = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))["tp"]
            check("params_sharded_over_tp",
                  all(s["shape"][1] == args.n_heads // tp for s in self.param_shards),
                  self.param_shards)

        parity = _flash_parity(self.segments, args, interpret=not on_tpu)
        check("flash_matches_plain", all(p["ok"] for p in parity.values()), parity)

        losses = [s["loss"] for s in self.steps]
        check("losses_finite_and_falling",
              all(x == x and abs(x) != float("inf") for x in losses) and losses[-1] < losses[0],
              losses)
        warm = self.steps[WARMUP_STEPS - 1]
        check("no_compilation_after_warmup",
              self.steps[-1][_COMPILE] == warm[_COMPILE],
              [s[_COMPILE] for s in self.steps])
        first = {k: self.steps[0][k] - self.at_built[k] for k in (_HIT, _MISS, _COMPILE)}
        entries = _count_entries(self.cache_dir)
        expected_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
        check("cache_dir_is_the_placed_one", self.cache_dir == expected_dir,
              {"in_use": self.cache_dir, "expected": expected_dir})
        check("cache_consulted_and_filled", first[_HIT] + first[_MISS] >= 1 and entries >= 1,
              {"first_step": first, "entries": entries})
        steady = sorted(s["seconds"] for s in self.steps[WARMUP_STEPS:])
        return {
            "ok": all(c["ok"] for c in checks),
            "failed": [c["check"] for c in checks if not c["ok"]],
            "pid": os.getpid(),
            "device": {
                "platform": platform,
                "kind": devices[0].device_kind,
                "count": len(jax.devices()),
            },
            "mesh": dict(zip(self.mesh.axis_names, self.mesh.devices.shape)),
            "config": {k: getattr(args, k) for k in (
                "vocab_size", "d_model", "n_heads", "d_ff", "n_layers", "seq_len",
                "batch_size", "dtype", "attention", "train_steps")},
            "params": int(self.params),
            "losses": losses,
            "not_a_benchmark": {
                "first_step_seconds": round(self.steps[0]["seconds"], 3),
                "steady_step_seconds_median": round(steady[len(steady) // 2], 4),
                "peak_bytes_in_use": peak,
                "bytes_in_use_after_step_1": self.bytes_in_use,
            },
            "first_step_cache": first,
            "inspection_recompiled": recompiled,
            "cache_dir": self.cache_dir,
            "cache_entries": {"before": self.cache_entries_before, "after": entries},
            "mosaic_calls": len(mosaic),
            "pack_efficiency": round(obs.gauge("text_pack_efficiency").value, 3),
            "native_io": native_io.build_info() or "python codec",
            "checks": checks,
        }


def _struct(x):
    import jax

    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)


def _shards(x):
    return [
        {"device": s.device.id, "shape": list(s.data.shape),
         "index": [[sl.start, sl.stop] for sl in s.index]}
        for s in x.addressable_shards
    ]


def _count_entries(path):
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def _mosaic_calls(hlo):
    """Every Mosaic custom call of the compiled module: kernel name and the
    shape of its first result (``[batch*heads, seq, head_dim]`` for flash)."""
    import re

    calls = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        # the pallas_call's name= is the last scope of the instruction's op_name
        name = re.search(r'op_name="[^"]*?([^/"]+)/pallas_call"', line)
        shape = re.search(r"[a-z0-9]+\[(\d+(?:,\d+)*)\]", line.split("custom-call(")[0])
        calls.append({
            "kernel": name.group(1) if name else None,
            "shape": [int(d) for d in shape.group(1).split(",")] if shape else None,
        })
    return calls


def _qkv_all_gathers(hlo, seq_len, head_dim):
    """all-gather instructions whose result ends in ``[..., seq, head_dim]`` —
    what XLA would insert to feed an unpartitioned attention call."""
    import re

    tail = re.compile(r"\[(?:\d+,)*{},{}\]".format(seq_len, head_dim))
    return [
        line.strip()[:200] for line in hlo.splitlines()
        if re.search(r"\ball-gather(-start)?\(", line) and tail.search(line.split("all-gather")[0])
    ]


def _flash_parity(segments, args, interpret):
    """``flash_attention(segment_ids=...)`` against ``plain_attention`` on
    this device at the smoke's heads × seq × head_dim, one packed row of the
    real batch: values and all three gradients. Tolerance from the dtype:
    four bf16 ulps of each tensor's largest magnitude."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.ops.flash_attention import flash_attention
    from tensorflowonspark_tpu.parallel.ring_attention import plain_attention

    shape = (1, args.n_heads, args.seq_len, args.d_model // args.n_heads)
    dtype = jnp.dtype(args.dtype)
    keys = jax.random.split(jax.random.PRNGKey(CORPUS_SEED), 4)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype) for kk in keys[:3])
    weight = jax.random.normal(keys[3], shape, jnp.float32)
    seg = jnp.asarray(segments, jnp.int32)

    def outputs(attention):
        def loss(q, k, v):
            out = attention(q, k, v)
            return (out.astype(jnp.float32) * weight).sum(), out

        (_, out), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out,) + grads

    got = outputs(lambda q, k, v: flash_attention(
        q, k, v, causal=True, segment_ids=seg, interpret=interpret))
    want = outputs(lambda q, k, v: plain_attention(q, k, v, causal=True, segment_ids=seg))
    tol = 4 * float(jnp.finfo(dtype).eps)
    result = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err, scale = float(jnp.abs(a - b).max()), float(jnp.abs(b).max())
        result[name] = {"max_err": err, "scale": scale, "ok": err <= tol * scale}
    return result


def smoke_main(args, ctx):
    """The cluster's ``main_fun``: the example's trainer under the probe."""
    import transformer_spark

    probe = _Probe(args)
    transformer_spark.main_fun(args, ctx, observer=probe)
    report = probe.finish()
    with open(args.report_path, "w") as f:
        json.dump(report, f)
    if not report["ok"]:
        raise AssertionError("chip smoke checks failed: {}".format(report["failed"]))


# -- the driver ---------------------------------------------------------------


def _executor_probe(_):
    return [{"pid": os.getpid(), "jax_imported": "jax" in sys.modules}]


def _started_processes():
    """Every process this script started and has not reaped: they share its
    process group. Its own multiprocessing resource tracker is left out —
    that one ends with the script, and is still needed until then."""
    from multiprocessing import resource_tracker

    spared = {os.getpid(), getattr(resource_tracker._resource_tracker, "_pid", None)}
    group, members = os.getpgrp(), []
    for entry in os.listdir("/proc"):
        if entry.isdigit() and int(entry) not in spared:
            try:
                if os.getpgid(int(entry)) == group:
                    members.append(int(entry))
            except OSError:
                pass
    return members


def _kill_started():
    for pid in _started_processes():
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass


def _on_deadline():
    sys.stderr.write("chip_smoke: not finished after {}s; stopping\n".format(DEADLINE_SECS))
    sys.stderr.flush()
    _kill_started()
    os._exit(124)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cpu", action="store_true",
                        help="rehearse on the CPU at toy widths; never a pass for the chip")
    own, example_flags = parser.parse_known_args()
    if os.getpgrp() != os.getpid():
        os.setpgrp()
    timer = threading.Timer(DEADLINE_SECS, _on_deadline)
    timer.daemon = True
    timer.start()
    started = time.time()

    import transformer_spark
    from tensorflowonspark_tpu import TFCluster
    from tensorflowonspark_tpu.backends.local import LocalSparkContext

    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    args = transformer_spark.build_parser().parse_args(
        (TINY if own.cpu else FULL)
        + ["--train_steps", str(WARMUP_STEPS + MEASURED_STEPS), "--log_steps", "1"]
        + example_flags
    )
    args.platform = "cpu" if own.cpu else "tpu"
    args.data_dir = os.path.join(OUT, "corpus")
    transformer_spark.make_text_corpus(
        args.data_dir, records_per_shard=2048, seed=CORPUS_SEED
    )
    env = {"JAX_PLATFORMS": args.platform}
    if own.cpu:
        devices = 1
        for size in (transformer_spark.parse_mesh(args.mesh) or {}).values():
            devices *= size
        env["TOS_NUM_CPU_DEVICES"] = str(devices)
        # a CPU process gets a compile cache only where the variable names
        # one, and toy programs compile in under the cache's one-second floor
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(OUT, "jax_cache")
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

    reports = []
    sc = LocalSparkContext(1)
    try:
        for run in (1, 2):
            args.report_path = os.path.join(OUT, "run{}.json".format(run))
            cluster = TFCluster.run(
                sc, smoke_main, args, 1,
                input_mode=TFCluster.InputMode.TENSORFLOW, master_node="chief", env=env,
            )
            try:
                cluster.shutdown(timeout=DEADLINE_SECS)  # raises on a node error
            finally:
                if os.path.exists(args.report_path):
                    with open(args.report_path) as f:
                        reports.append(json.load(f))
                    print(json.dumps(reports[-1]), flush=True)
        executor = sc.parallelize([0], 1).mapPartitions(_executor_probe).collect()[0]
    finally:
        sc.stop()
        stragglers = _started_processes()
        _kill_started()

    first, second = reports
    checks = {
        "driver_never_imported_jax": "jax" not in sys.modules,
        "executor_never_imported_jax": not executor["jax_imported"],
        "one_child_per_run": len({first["pid"], second["pid"], executor["pid"], os.getpid()}) == 4,
        "second_run_reacquired_device": second["device"] == first["device"],
        "second_run_first_step_from_cache": (
            second["first_step_cache"][_HIT] >= 1 and second["first_step_cache"][_MISS] == 0
        ),
        "cache_kept_entries": second["cache_entries"]["before"] >= first["cache_entries"]["after"] >= 1,
    }
    summary = {
        "ok": all(checks.values()),
        "driver_checks": checks,
        "device": first["device"],
        "first_step_seconds": {
            "run1": first["not_a_benchmark"]["first_step_seconds"],
            "run2_warm_cache": second["not_a_benchmark"]["first_step_seconds"],
        },
        "run1_cache_entries_before": first["cache_entries"]["before"],
        "stragglers_killed": len(stragglers),
        "wall_seconds": round(time.time() - started, 1),
        "reports": [os.path.relpath(os.path.join(OUT, "run{}.json".format(n)), ROOT) for n in (1, 2)],
    }
    print(json.dumps(summary), flush=True)
    if not summary["ok"]:
        sys.exit("chip_smoke: driver checks failed: {}".format(
            [name for name, ok in checks.items() if not ok]))
    print(json.dumps({"ok": True, "device": first["device"]}), flush=True)


if __name__ == "__main__":
    main()
