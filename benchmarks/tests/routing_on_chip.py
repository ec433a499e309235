"""Read, on the chip and at a routed cell's own size, what its rate rests on
from seed to seed: for each seed the held experts' share of the slots in every
one of the job's first steps (checked, warm-up and a window's worth), the
fullest held expert over the mean, and the rate over the last ``--window``
steps; for the first seed also the smallest of the program's first-gradient
norms leaf by leaf (the leaves the reference leaves out of the comparison
because its own AdamW cannot see their gradient are among them) and, with
``--reference``, the reference's norm and root mean square beside each. One
process holds the chip for all the seeds. Not part of a benchmark run and not
run by pytest:

    python3 benchmarks/tests/routing_on_chip.py --workload xing4-a4b.packed8k \
        --seeds 2600000901,2600000902,2600000903 --steps 23 --window 18

Prints one JSON line per seed; PERF.md records the readings.
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


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--steps", type=int, default=23)
    parser.add_argument("--window", type=int, default=18, help="the last steps, timed")
    parser.add_argument("--reference", action="store_true", help="follow the first seed's first step too")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    from benchmarks import child, run
    from tensorflowonspark_tpu import util

    util.place_compile_cache()
    import numpy as np

    _, cell, config, traffic = run.resolve(args.workload, args.rehearse)
    ctx = types.SimpleNamespace(initialize_distributed=lambda: None, num_processes=1, num_workers=1, executor_id=0)
    family = importlib.import_module("benchmarks.families." + config["family"])
    reference = importlib.import_module("benchmarks.reference." + config["family"])
    for index, seed in enumerate(int(s) for s in args.seeds.split(",")):
        scratch = os.path.join(ROOT, ".bench_scratch", "routing." + cell["name"])
        shutil.rmtree(scratch, ignore_errors=True)
        os.makedirs(scratch)
        spec = {"workload": cell["name"], "chips": cell["chips"], "seed": seed, "config": config,
                "traffic": traffic, "scratch": scratch, "rehearse": args.rehearse}
        parts = {}
        t0 = time.time()
        job = family.build(spec, ctx, parts)
        loop = child.Loop(job)
        loop.advance()
        loop.fence()
        first_s = time.time() - t0
        program = job.first_grad(loop.state)[0] if index == 0 else None
        carried = [loop.metrics]
        for step in range(1, args.steps):
            if step == args.steps - args.window:
                loop.fence()
                t_window = time.perf_counter()
            loop.advance()
            carried.append(loop.metrics)
        loop.fence()
        window_s = time.perf_counter() - t_window
        held = [100.0 * float(m["counter/moe_slots_held"]) / float(m["counter/moe_slots_routed"]) for m in carried]
        line = {
            "workload": cell["name"], "seed": seed, "held_pct_by_step": [round(h, 3) for h in held],
            "held_pct_window": float(np.mean(held[-args.window:])),
            "load_max_over_mean_by_step": [round(float(m["gauge/moe_expert_load_max_over_mean"]), 3) for m in carried],
            "losses": [round(float(m["loss"]), 4) for m in carried],
            "rate_per_chip": args.window * job.units_per_step / job.chips / window_s,
            "balance_s": parts.get("balance_s"), "state_s": parts.get("state_s"), "to_first_step_s": first_s,
        }
        print(json.dumps(line), flush=True)
        kept, follow = child.release(loop)
        del loop, job, carried
        if program is not None:
            want = follow(kept[:1])["first_grad"] if args.reference else {}
            sizes = {"/".join(path): float(np.sqrt(np.prod(shape))) if shape else 1.0
                     for path, (shape, _) in reference.leaf_shapes(config).items()}
            leaves = {name: [program[name], want.get(name), None if name not in want else want[name] / sizes[name]]
                      for name in sorted(program, key=program.get)[:40]}
            print(json.dumps({"seed": seed, "first_grad_program_reference_rms": leaves}), flush=True)
        shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    main()
