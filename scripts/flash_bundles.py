"""What a computed block of the flash kernels costs, read from the chip's
compiler (no chip: sizes of a schedule, never times).

The TPU compiler installed here compiles for a described v5e and, asked by
``LIBTPU_INIT_ARGS="--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true"``,
writes every kernel's final schedule: ``*-<kernel>.1-NN-final_bundles.txt``
(one VLIW bundle a line, control targets marked) and
``*-NN-final_hlo-static-per-bundle-utilization.txt`` (per bundle, how many
slots of each unit it fills). The region under ``pl.when(ITEM_COMPUTE)`` is
straight-line code between two control targets, the one that holds the
``vmatmul``s; its bundle count over 940 MHz is the time of a needed block
inside a walk that PR 29 read on the chip (PERF.md §7). A unit's column
summed over the region, over the unit's slots a bundle, is the bundles that
unit would need alone: the region can be no shorter than the fullest.

    python scripts/flash_bundles.py                 # the four LM cells' shapes
    python scripts/flash_bundles.py --cell lm1024   # one of them
    python scripts/flash_bundles.py --repo <dir>    # another checkout's kernels

Each shape compiles in a process of its own, which aborts once the program
is compiled, after the kernels' files are written (the dump's own doing: a
report's template is not installed here): the parent reads what is there and
takes no notice of the exit code. A builder's tool: no test collects it.
"""

import argparse
import glob
import math
import os
import re
import shutil
import subprocess
import sys
import tempfile

CLOCK_GHZ = 0.94  # a v5e's

#: q's shape, key/value heads (None: as many as q's), v's head size (None: q's), the rule
CELLS = {
    "lm1024": ((4, 16, 4096, 64), None, None, "causal"),  # lm1024.packed4k
    "xing4-a4b": ((1, 32, 8192, 192), None, 128, "causal"),  # xing4-a4b.packed8k
    "sdar-30b-a3b": ((2, 32, 8192, 128), 4, None, "block_diffusion"),  # sdar-30b-a3b.bd4-packed4k
    "laguna-s-2-1": ((1, 72, 8192, 128), 8, None, "window"),  # laguna-s-2-1.code8k's sliding layers
}
WINDOW = 512  # the ``window`` rule's

#: a bundle's line: its number, a control target's mark if it is one, the loop's depth, the operations
_BUNDLE = re.compile(r"^\s*(0x[0-9a-f]+|\d+)\s+(LH|LB|LE|PB|PF|CT)?:[\s>]*\{")


def _compile(cell):
    """Child: forward and backward of one cell's attention call, compiled for
    a described v5e (the dump flags are in the environment)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.ops.flash_attention import flash_attention

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    shape, kv_heads, value_dim, rule = CELLS[cell]
    kv = shape if kv_heads is None else (shape[0], kv_heads) + shape[2:]
    array = lambda s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)  # noqa: E731
    ids = array((shape[0], shape[2]), jnp.int32)

    def loss(q, k, v, seg, labels):
        if rule == "block_diffusion":
            o = flash_attention(q, k, v, segment_ids=seg, labels=labels, rule=rule)
        elif rule == "window":
            o = flash_attention(q, k, v, causal=True, segment_ids=seg, rule=rule, window=WINDOW)
        else:
            o = flash_attention(q, k, v, causal=True, segment_ids=seg)
        return (o.astype(jnp.float32) ** 2).sum()

    jax.jit(jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))).lower(
        array(shape), array(kv), array(kv[:3] + (value_dim or kv[3],)), ids, ids).compile()


def compute_region(bundles):
    """``(first, last)`` bundle numbers of the straight-line region that holds
    the products: from the control target before the first ``vmatmul`` to the
    one after the last."""
    marks, products = [], []
    for line in bundles:
        at = _BUNDLE.match(line)
        if at is None:
            continue
        number = int(at.group(1), 0)
        if at.group(2):
            marks.append(number)
        if "vmatmul" in line:
            products.append(number)
    if not products:
        raise ValueError("no vmatmul in the schedule")
    first = max(m for m in marks if m <= products[0])
    last = min([m for m in marks if m > products[-1]] or [number + 1])
    inside = [m for m in marks if first < m < last]
    if inside:
        raise ValueError("the products span control targets {}: not one straight-line region".format(inside))
    return first, last


def unit_table(utilization, first, last):
    """``{unit: bundles the unit would need alone}`` over bundles ``[first, last)``."""
    lines = utilization.splitlines()
    names = [name.strip() for name in lines[1].split(",")]
    slots = [int(x) for x in lines[2].split()]
    rows = [[int(x) for x in line.split()] for line in lines[4:] if line.strip()]
    return {name: math.ceil(sum(row[i] for row in rows[first:last]) / slots[i]) for i, name in enumerate(names)}


def read_kernels(dump_dir):
    """``{kernel: (bundles of its compute region, its unit table)}`` of every
    ``flash_*`` kernel whose final schedule is in ``dump_dir``."""
    found = {}
    for path in sorted(glob.glob(os.path.join(dump_dir, "*-flash_*-final_bundles.txt"))):
        if "schedule-analysis" in path:
            continue
        stem, kernel = re.match(r"(.*-(flash_\w+)\.\d+)-\d+-final_bundles\.txt$", path).groups()
        with open(path) as f:
            first, last = compute_region(f.read().splitlines())
        with open(glob.glob(stem + "-*-final_hlo-static-per-bundle-utilization.txt")[0]) as f:
            found[kernel] = (last - first, unit_table(f.read(), first, last))
    return found


def measure(cell, repo, keep=None):
    dump_dir = tempfile.mkdtemp(prefix="flash_bundles_")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
                   LIBTPU_INIT_ARGS="--xla_jf_dump_to={} --xla_jf_dump_llo_text=true".format(dump_dir))
        run = subprocess.run([sys.executable, os.path.abspath(__file__), "--child", cell],
                             env=env, cwd=repo, capture_output=True, text=True)
        found = read_kernels(dump_dir)
        if len(found) != 2:  # the abort once all is compiled is the dump's; a kernel that did not compile is ours
            raise RuntimeError("{}: the schedules of {} and not of two kernels:\n{}".format(
                cell, sorted(found), run.stderr[-4000:]))
        if keep:
            for path in glob.glob(os.path.join(dump_dir, "*-flash_*final*")):
                shutil.copy(path, keep)
        return found
    finally:
        shutil.rmtree(dump_dir, ignore_errors=True)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cell", action="append", choices=sorted(CELLS), help="default: all four")
    parser.add_argument("--repo", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        help="the checkout whose kernels are compiled (default: this one)")
    parser.add_argument("--keep", help="a directory to copy the kernels' final schedules into")
    parser.add_argument("--child", help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child:
        return _compile(args.child)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
    units = ("MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD:FILL", "VSTORE", "VSTORE:SPILL")
    print("| cell, shape | kernel | compute region (bundles) | / {} GHz (us) | {} |".format(CLOCK_GHZ, " | ".join(units)))
    print("|---|---|---|---|{}".format("---|" * len(units)))
    for cell in args.cell or sorted(CELLS):
        shape, kv_heads, value_dim, _ = CELLS[cell]
        described = "{} x {}{} x {} x {}{}".format(
            shape[0], shape[1], "" if kv_heads is None else "/{}".format(kv_heads), shape[2], shape[3],
            "" if value_dim is None else "/{}".format(value_dim))
        for kernel, (bundles, table) in sorted(measure(cell, os.path.abspath(args.repo), args.keep).items(), reverse=True):
            print("| {}, {} | {} | {} | {:.2f} | {} |".format(
                cell, described, kernel, bundles, bundles / CLOCK_GHZ / 1e3,
                " | ".join("{} ({:.0f}%)".format(table[u], 100.0 * table[u] / bundles) for u in units)))


if __name__ == "__main__":
    main()
