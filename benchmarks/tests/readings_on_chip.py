"""Read, on the chip and at a cell's own size, the numbers its limits are set
from: for each seed the program's first steps against the reference (sound
runs), and for the first ``--control`` seeds the float8 control against the
reference. One process holds the chip for all the seeds, so set-up is paid
once. Not part of a benchmark run and not run by pytest:

    python3 benchmarks/tests/readings_on_chip.py --workload lm1024.packed4k \
        --seeds 11,12,13,14 --control 3

Prints one JSON line per seed; PERF.md records what the limits were set from.
"""

import argparse
import importlib
import json
import os
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _leaf_distances(got, want):
    """``{leaf: [|S got - S want|, |S want|, |want|]}`` of the first gradient."""
    out = {}
    for name, ref in want["first_grad_sketch"].items():
        mine = got["first_grad_sketch"][name]
        out[name] = [sum((a - b) ** 2 for a, b in zip(mine, ref)) ** 0.5, sum(b * b for b in ref) ** 0.5,
                     want["first_grad"][name]]
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--control", type=int, default=3, help="how many of the seeds also run the float8 control")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--detail", help="file to append each reading's per-leaf sketch distances to (JSON lines)")
    args = parser.parse_args()

    from benchmarks import check, child, run
    from tensorflowonspark_tpu import util

    util.place_compile_cache()
    import jax

    _, cell, config, traffic = run.resolve(args.workload, args.rehearse)
    if not args.rehearse and (jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell["chips"]):
        sys.exit("needs {} TPU chip(s)".format(cell["chips"]))
    ctx = types.SimpleNamespace(initialize_distributed=lambda: None, num_processes=1, num_workers=1, executor_id=0)
    family = importlib.import_module("benchmarks.families." + config["family"])
    for index, seed in enumerate(int(s) for s in args.seeds.split(",")):
        scratch = os.path.join(ROOT, ".bench_scratch", "readings." + cell["name"])
        shutil.rmtree(scratch, ignore_errors=True)
        os.makedirs(scratch)
        spec = {"workload": cell["name"], "chips": cell["chips"], "seed": seed, "config": config,
                "traffic": traffic, "scratch": scratch, "rehearse": args.rehearse}
        t0 = time.time()
        job = family.build(spec, ctx, {})
        loop = child.Loop(job)
        program = child.checked_steps(loop, traffic["check_steps"], {})
        kept, reference = child.release(loop)
        del loop, job
        t1 = time.time()
        want = reference(kept)
        t2 = time.time()
        line = {"workload": cell["name"], "seed": seed, "sound": check.readings(program, want),
                "program_losses": program["losses"], "reference_losses": want["losses"],
                "program_s": t1 - t0, "reference_s": t2 - t1}
        if index < args.control:
            control = reference(kept, quant="fp8")
            line["control"] = check.readings(control, want)
            line["control_losses"] = control["losses"]
            line["control_s"] = time.time() - t2
        shutil.rmtree(scratch, ignore_errors=True)
        print(json.dumps(line), flush=True)
        if args.detail:
            sides = {"sound": program, "control": control} if index < args.control else {"sound": program}
            with open(args.detail, "a") as f:
                for side, got in sides.items():
                    f.write(json.dumps({"workload": cell["name"], "seed": seed, "side": side,
                                        "leaves": _leaf_distances(got, want)}) + "\n")


if __name__ == "__main__":
    main()
