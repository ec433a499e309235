"""Show, on the chip, that the program's stall counter fires when the device is
late and stays still when the host is: the evidence ``*_step_stall_pct``
stands on, since a clean benchmark window reads 0 either way. Not part of a
benchmark run and not run by pytest:

    python3 benchmarks/tests/stall_on_chip.py            # a TPU
    python3 benchmarks/tests/stall_on_chip.py --cpu      # rehearsal, tiny sizes

The loop is the cells' (``child.Loop``): at most two steps in flight, the
program's own ``compile_train_step`` callable. Four windows of ``--steps``
steps, each opened by a fence like the benchmark's:

* ``clean``: nothing planted;
* ``device_late``: before ``--plant`` of the steps another program that keeps
  the chip busy for about ``--late`` seconds (timed alone first) is put into
  the device's queue,
  so the steps behind it finish late and the host waits for them;
* ``host_late``: the loop sleeps ``--late`` seconds before the same steps
  (a slow input): the queue runs dry, the device is not at fault;
* ``clean_again``: nothing planted, the history now holding all of the above.

Prints one JSON line per window: the counters' movement, and
``step_stall_pct`` read by the benchmark's own reader.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

COUNTERS = ("train_steps_dispatched_total", "train_step_stalls_total", "train_step_stall_seconds_total")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true", help="rehearse on the CPU at tiny sizes")
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--plant", type=int, default=3, help="how many steps of a planted window are made late")
    parser.add_argument("--late", type=float, default=1.0, help="seconds each planted delay lasts")
    args = parser.parse_args()

    from tensorflowonspark_tpu import obs, util

    if args.cpu:
        util.force_platform("cpu")
    util.place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks import run
    from tensorflowonspark_tpu import parallel
    from tensorflowonspark_tpu.train import SyncDataParallel

    platform = jax.devices()[0].platform
    if not args.cpu and platform != "tpu":
        sys.exit("needs a TPU chip (or --cpu for a rehearsal)")
    width, rows, layers = (512, 2048, 6) if args.cpu else (4096, 8192, 8)

    def loss_fn(params, batch):
        x = batch["x"]
        for w in params["ws"]:
            x = jnp.tanh(x @ w.astype(jnp.bfloat16))
        return jnp.mean(x.astype(jnp.float32) ** 2)

    strategy = SyncDataParallel(parallel.build_mesh({"dp": 1}, devices=jax.devices()[:1]))
    optimizer = optax.sgd(1e-3)
    key = jax.random.PRNGKey(0)
    state = strategy.create_state(
        lambda: {"ws": [jax.random.normal(key, (width, width), jnp.float32) / width ** 0.5] * layers}, optimizer)
    step = strategy.compile_train_step(loss_fn, optimizer)
    batch = strategy.shard_batch({"x": np.ones((rows, width), jnp.bfloat16)})

    @jax.jit
    def hog(x, n):
        """``n`` dependent matrix products: the chip is busy and nothing of the step's is."""
        return jax.lax.fori_loop(0, n, lambda _, a: jnp.tanh(a @ a) * 0.5, x)

    hog_x = jnp.ones((width, width), jnp.bfloat16)
    jax.block_until_ready(hog(hog_x, 8))
    t0 = time.perf_counter()
    jax.block_until_ready(hog(hog_x, 256))
    per_product = (time.perf_counter() - t0) / 256
    hog_n = max(1, int(args.late / per_product))
    t0 = time.perf_counter()
    jax.block_until_ready(hog(hog_x, hog_n))
    hog_s = time.perf_counter() - t0

    pending = []

    def advance(before_dispatch=None):
        nonlocal state
        if len(pending) >= 2:
            jax.block_until_ready(pending.pop(0))
        if before_dispatch is not None:
            before_dispatch()
        state, metrics = step(state, batch)
        pending.append(metrics["loss"])

    def fence():
        jax.block_until_ready(pending)
        del pending[:]

    def counters():
        snap = obs.snapshot()["counters"]
        return {name: snap[name]["value"] for name in COUNTERS}

    for _ in range(8):  # compile, warm up
        advance()
    fence()
    t0 = time.perf_counter()
    for _ in range(8):
        advance()
    fence()
    step_s = (time.perf_counter() - t0) / 8
    print(json.dumps({"platform": platform, "kind": jax.devices()[0].device_kind, "step_ms": 1e3 * step_s,
                      "hog_products": hog_n, "hog_s": hog_s}), flush=True)

    reader = run.reader("per_layer", "lm_step_stall_pct")
    planted_at = set(range(10, 10 + 8 * args.plant, 8))
    plants = {
        "clean": None,
        "device_late": lambda: hog(hog_x, hog_n),
        "host_late": lambda: time.sleep(args.late),
        "clean_again": None,
    }
    ok = True
    for name, plant in plants.items():
        fence()
        before, t0 = counters(), time.perf_counter()
        for i in range(args.steps):
            advance(plant if plant is not None and i in planted_at else None)
        fence()
        seconds = time.perf_counter() - t0
        moved = {k: v - before[k] for k, v in counters().items()}
        record = {"window": {"seconds": seconds, "counters": moved}}
        late_s = 0.0 if plant is None else hog_s if name == "device_late" else args.late
        line = {"window": name, "seconds": seconds, "planted": len(planted_at) if plant else 0,
                "late_s": late_s, "step_stall_pct": reader(record)}
        line.update(moved)
        if name == "device_late":
            # every planted delay is found, and booked at about its length
            found = moved["train_step_stalls_total"] == len(planted_at)
            booked = moved["train_step_stall_seconds_total"] / (len(planted_at) * hog_s)
            line["booked_over_planted"] = booked
            ok = ok and found and 0.7 < booked < 1.3
        else:
            ok = ok and moved["train_step_stalls_total"] == 0
        print(json.dumps(line), flush=True)
    print(json.dumps({"ok": ok, "platform": platform}))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
