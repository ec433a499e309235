"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that stays off jax: it resolves the cell (``BENCHMARK.json`` ->
``configs/<config>.json``, ``traffic/<traffic>.json``), launches the job the
way a user of the system does — ``TFCluster.run`` on the local backend, one
executor, one spawned jax child that holds the cell's chips and runs
``child.main_fun`` — and prints the contract's JSON object as the last line
of its standard output. A run that finds no TPU, fewer chips than the cell
asks for, or a device that ``peaks.json`` does not know prints no result and
exits non-zero.

``--rehearse`` is the builder's CPU rehearsal: the same program at the toy
widths of ``rehearse.json`` with the kernels interpreted; its metrics carry a
``cpu.`` prefix and ``correct`` is false — it is never a measurement.

A name that is not in ``BENCHMARK.json`` is read as ``<config>.<traffic>`` so
a probe can run from a scratch traffic file without a ``workloads`` entry.
"""

import argparse
import importlib.util
import json
import os
import shutil
import signal
import sys
import threading
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: a cell's first run in a checkout compiles and may take 1200 s in all
DEADLINE_SECS = 1150


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def resolve(name, rehearse=False):
    """The cell's spec: its entry in ``BENCHMARK.json`` and its two files."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        config, _, traffic = name.partition(".")
        cell = {"name": name, "config": config, "traffic": traffic, "chips": None}
    config = _load("configs", cell["config"] + ".json")
    traffic = _load("traffic", cell["traffic"] + ".json")
    if rehearse:
        toy = _load("rehearse.json")[config["family"]]
        config, traffic = _merge(config, toy["config"]), _merge(traffic, toy["traffic"])
    chips = 1
    for size in traffic["mesh"].values():
        chips *= size
    if cell["chips"] not in (None, chips):
        raise ValueError("cell {} asks for {} chips but its mesh {} has {}".format(
            name, cell["chips"], traffic["mesh"], chips))
    return bench, dict(cell, chips=chips), config, traffic


def reader(kind, name):
    """``read(run)`` of ``<kind>/<name>.py`` (a file of its own per metric)."""
    path = os.path.join(HERE, {"per_layer": "layer_metrics"}.get(kind, kind), name + ".py")
    spec = importlib.util.spec_from_file_location("benchmarks.{}.{}".format(kind, name.replace(".", "_")), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics_of(bench, kind, run):
    """Every ``kind`` metric of ``BENCHMARK.json`` that this cell reports. A
    metric's ``workloads`` list decides (a probe, which has no entry, counts as
    the cells of its configuration); a reader that finds nothing to read
    returns None and is left out."""
    cells = {w["name"]: w["config"] for w in bench["workloads"]}
    config = cells.get(run["workload"], run["workload"].partition(".")[0])
    out = {}
    for metric in bench[kind]:
        listed = metric.get("workloads")
        if listed is not None and run["workload"] in cells and run["workload"] not in listed:
            continue
        if listed is not None and config not in (cells.get(name) for name in listed):
            continue
        value = reader(kind, metric["name"])(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def _started_processes():
    """Every process this run started and has not reaped: they share its
    process group (its own multiprocessing resource tracker is spared — it
    ends with the script and is needed until then)."""
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
    pids = _started_processes()
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    for pid in pids:
        try:
            os.waitpid(pid, 0)
        except OSError:
            pass  # not our child (a grandchild): init reaps it
    deadline = time.time() + 10
    while time.time() < deadline and any(os.path.exists("/proc/{}".format(p)) and _alive(p) for p in pids):
        time.sleep(0.05)


def _alive(pid):
    try:
        with open("/proc/{}/stat".format(pid)) as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def _on_deadline():
    sys.stderr.write("benchmarks/run.py: not finished after {}s; stopping\n".format(DEADLINE_SECS))
    sys.stderr.flush()
    _kill_started()
    os._exit(124)


def launch(spec, env):
    """``TFCluster.run`` on the local backend: one executor, one jax child."""
    from benchmarks import child
    from tensorflowonspark_tpu import TFCluster
    from tensorflowonspark_tpu.backends.local import LocalSparkContext

    sc = LocalSparkContext(1)
    try:
        cluster = TFCluster.run(
            sc, child.main_fun, spec, 1,
            input_mode=TFCluster.InputMode.TENSORFLOW, master_node="chief", env=env,
        )
        cluster.shutdown(timeout=DEADLINE_SECS)  # raises on a node error
    finally:
        sc.stop()
        _kill_started()


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU rehearsal at toy widths; prints cpu.* metrics and correct: false")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    bench, cell, config, traffic = resolve(args.workload, args.rehearse)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]

    if os.getpgrp() != os.getpid():
        os.setpgrp()
    timer = threading.Timer(DEADLINE_SECS, _on_deadline)
    timer.daemon = True
    timer.start()

    scratch = os.path.join(ROOT, ".bench_scratch", cell["name"])
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    spec = {
        "workload": cell["name"], "chips": cell["chips"], "seed": args.seed, "seconds": seconds,
        "trace": bool(args.trace), "rehearse": args.rehearse, "config": config, "traffic": traffic,
        "scratch": scratch,
    }
    # deployment settings of the cell (cache sizes and the like) ride the env
    # lane into the jax child, as a user's would
    env = dict(traffic.get("env", {}), JAX_PLATFORMS="tpu")
    if args.rehearse:
        env.update(JAX_PLATFORMS="cpu", TOS_NUM_CPU_DEVICES=str(cell["chips"]))
    load_at_start = os.getloadavg()[0]
    try:
        launch(spec, env)
    finally:
        result_path = os.path.join(scratch, "result.json")
        result = None
        if os.path.exists(result_path):
            with open(result_path) as f:
                result = json.load(f)
        for leftover in ("corpus", "slabs"):
            shutil.rmtree(os.path.join(scratch, leftover), ignore_errors=True)
    if result is None or "error" in result:
        sys.exit("benchmarks/run.py: the job gave no result\n{}".format((result or {}).get("error", "")))

    parts = dict(result["parts"], launch_s=result["t_enter"] - T0, setup_s=result["t_window"] - T0)
    window = result["window"]
    run = dict(
        result, workload=cell["name"], chips=cell["chips"], family=config["family"],
        config=config, traffic=traffic, parts=parts,
    )
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = metrics_of(bench, kind, run)
    if args.rehearse:
        metrics = {"cpu." + k: v for k, v in metrics.items()}
    print("bench.parts " + json.dumps(dict(
        parts, cpu_count=os.cpu_count(), loadavg_start=load_at_start, loadavg_window_end=window["loadavg_end"],
        window_s=window["seconds"], steps=window["steps"], spans=window["spans"],
        consumer_wait_s=window["counters"].get("data_consumer_wait_seconds_total"),
        step_memory=result["step_memory"], program_losses=result["program_losses"],
        reference_losses=result["reference_losses"], check=result["check"],
    )))
    print("bench.counters " + json.dumps({k: v for k, v in window["counters"].items() if v}))
    print("bench.gauges " + json.dumps(window["gauges"]))
    per_second = [0] * (int(window["seconds"]) + 1)
    for t in window["dispatch_at"]:
        per_second[min(int(t), len(per_second) - 1)] += 1
    print("bench.steps_dispatched_per_second " + json.dumps(per_second))
    line = {
        "correct": bool(result["correct"]), "attempted": result["attempted"], "failed": result["failed"],
        "metrics": metrics, "device": result["device"],
    }
    if args.trace and result.get("trace"):
        line["breakdown"] = {
            "device_ops": result["trace"]["device_ops"], "idle_gaps": result["trace"]["idle_gaps"]}
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
