"""TPU topology discovery and chip-visibility control.

The TPU-native replacement for the reference's ``gpu_info.py`` (nvidia-smi
scraping + ``CUDA_VISIBLE_DEVICES`` pinning,
/root/reference/tensorflowonspark/gpu_info.py:54-116). TPUs need a different
model: a host owns all of its chips through libtpu (one process per host by
default), topology comes from the TPU runtime env / device files rather than a
CLI tool, and visibility is controlled with ``TPU_VISIBLE_CHIPS`` /
``TPU_PROCESS_BOUNDS`` instead of a device list.

Nothing here imports jax — these probes run in the lightweight executor
process before the jax child is forked.
"""

import glob
import logging
import math
import os

logger = logging.getLogger(__name__)

#: env vars consulted for explicit topology overrides
ENV_CHIP_COUNT = "TOS_TPU_CHIPS_PER_HOST"
ENV_ACCEL_TYPE = "TOS_TPU_ACCELERATOR_TYPE"

#: accelerator generation → (what the "-N" suffix counts, cores per chip,
#: max chips per host machine). Cloud TPU naming: core-counted generations
#: (v2..v4, v5p) say "v4-32" = 32 TensorCores = 16 chips; chip-counted
#: generations (v5e/v5litepod, v6e) say "v5e-32" = 32 chips. Rule-based so
#: ANY slice size derives (round-2 review: a fixed table stopped at v5p-16).
_GENERATIONS = {
    "v2": ("cores", 2, 4),
    "v3": ("cores", 2, 4),
    "v4": ("cores", 2, 4),
    "v5p": ("cores", 2, 4),
    "v5e": ("chips", 1, 8),
    "v5litepod": ("chips", 1, 8),
    "v6e": ("chips", 1, 8),
}


def parse_accelerator_type(accel_type):
    """``'v5e-32'`` → ``('v5e', 32)``; None for unparseable strings."""
    if not accel_type or "-" not in accel_type:
        return None
    gen, _, num = accel_type.partition("-")
    gen = gen.lower()
    if gen not in _GENERATIONS or not num.isdigit() or int(num) < 1:
        return None
    return gen, int(num)


def _device_file_chips():
    """Chips this machine can open: ``/dev/accel*`` (PCIe TPU driver) or the
    numbered VFIO groups under ``/dev/vfio`` (one per passed-through chip)."""
    accels = glob.glob("/dev/accel*")
    if accels:
        return len(accels)
    return sum(1 for p in glob.glob("/dev/vfio/*") if os.path.basename(p).isdigit())


def detect_local_chips():
    """Best-effort count of TPU chips attached to this host.

    Order: explicit override env → device files → TPU runtime env hints →
    0 (no TPU). Device files come before the env hints because the hints
    describe the host *type*, not what this machine was handed: a VM given
    one chip of a 2x2 v5e host still carries
    ``TPU_CHIPS_PER_HOST_BOUNDS=2,2,1`` while ``/dev/vfio`` holds one group
    and the runtime opens one device.
    """
    override = os.environ.get(ENV_CHIP_COUNT)
    if override:
        return int(override)
    chips = _device_file_chips()
    if chips:
        return chips
    # Cloud TPU VM runtime exports these
    for var in ("TPU_CHIPS_PER_HOST_BOUNDS", "TPU_CHIPS_PER_PROCESS_BOUNDS"):
        bounds = os.environ.get(var)
        if bounds:
            try:
                return math.prod(int(x) for x in bounds.split(","))
            except ValueError:
                pass
    return 0


def is_tpu_available():
    """Analogue of gpu_info.is_gpu_available (reference gpu_info.py:45)."""
    return detect_local_chips() > 0


def accelerator_type():
    """Accelerator type string (e.g. 'v5e-32') if known, else None."""
    return os.environ.get(ENV_ACCEL_TYPE) or os.environ.get("TPU_ACCELERATOR_TYPE")


def topology_for(accel_type):
    """(chips_per_host, total_chips) derived from the accelerator type, else
    None. Single-host slices put all chips on one machine; multi-host
    slices use the generation's per-host chip count (4 for core-counted
    generations, and for chip-counted ones past the 8-chip host boundary)."""
    parsed = parse_accelerator_type(accel_type)
    if parsed is None:
        return None
    gen, num = parsed
    unit, cores_per_chip, host_max = _GENERATIONS[gen]
    total_chips = num // cores_per_chip if unit == "cores" else num
    total_chips = max(total_chips, 1)
    if total_chips <= host_max:
        return (total_chips, total_chips)
    # multi-host: v5e/v6e multi-host slices are built from 4-chip hosts
    per_host = 4 if unit == "chips" else min(host_max, total_chips)
    return (per_host, total_chips)


def num_hosts_for(accel_type):
    """Host (worker VM) count for a slice, or None — what the launch tooling
    sizes ``--cluster_size`` with."""
    topo = topology_for(accel_type)
    if topo is None:
        return None
    per_host, total = topo
    return max(1, total // per_host)


def validate_against_runtime(local_device_count):
    """Compare the env/device-file detection against what the runtime
    actually sees (called from the jax child once jax is up). Logs — never
    raises — because detection feeds placement hints, not correctness.

    Core-counted generations (v2/v3) expose 2 devices per chip, so a
    runtime count of exactly 2x the detected chips is also a match."""
    detected = detect_local_chips()
    if not detected or not local_device_count:
        return True
    if local_device_count in (detected, 2 * detected):
        return True
    logger.warning(
        "tpu_info detected %d local chip(s) but the runtime reports %d "
        "local device(s); trusting the runtime (override with %s)",
        detected, local_device_count, ENV_CHIP_COUNT,
    )
    return False


def local_topology():
    """Summary dict of this host's TPU situation, shipped in the reservation
    record so the coordinator sees the whole slice's shape (SURVEY.md §2.8:
    the reservation server's role grows to include TPU topology exchange)."""
    accel = accelerator_type()
    chips = detect_local_chips()
    if chips == 0 and accel:
        derived = topology_for(accel)
        if derived:
            chips = derived[0]
    return {
        "accelerator_type": accel,
        "num_chips": chips,
        "worker_id": os.environ.get("TPU_WORKER_ID"),
        "worker_hostnames": os.environ.get("TPU_WORKER_HOSTNAMES"),
    }


def visibility_env(chip_ids=None, platform=None):
    """Environment to pin a child process to a subset of chips / a platform.

    The CUDA_VISIBLE_DEVICES analogue (reference gpu_info.py:102-113 placed
    workers on GPUs by local index). On TPU the common case is *all* chips to
    *one* process per host; chip subsetting is for megacore-style splits or
    colocated independent replicas (TFParallel).
    """
    env = {}
    if platform:
        env["JAX_PLATFORMS"] = platform
    if chip_ids is not None:
        env["TPU_VISIBLE_CHIPS"] = ",".join(str(c) for c in chip_ids)
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = _chip_grid_bounds(len(chip_ids))
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def local_chip_share(local_rank, num_local, platform=None):
    """Chip ids for the ``local_rank``-th of ``num_local`` jax processes
    that share this host, or None when no pinning applies (CPU platform, no
    chips detected, or a sole process, which owns them all). ``platform`` is
    what the children are told to use; unset, they inherit ``JAX_PLATFORMS``.

    A chip belongs to one process: co-resident children that each claimed
    every chip would collide inside libtpu — the second one fails or hangs
    at backend start-up. So the host's chips are split evenly and
    contiguously, and more processes than chips is refused here, before
    anything is spawned.
    """
    n_chips = detect_local_chips()
    platform = platform or os.environ.get("JAX_PLATFORMS", "")
    if not n_chips or platform.split(",")[0] == "cpu":
        return None
    if num_local > n_chips:
        raise RuntimeError(
            "{} jax processes placed on this host but it has {} TPU chip(s) — "
            "a chip belongs to one process; run one executor per host (it "
            "drives all of the host's chips) or at most one per chip".format(
                num_local, n_chips
            )
        )
    if num_local == 1:
        return None
    per = n_chips // num_local
    return list(range(local_rank * per, (local_rank + 1) * per))


def _chip_grid_bounds(n):
    """x,y,z bounds covering ``n`` chips — the per-process bounds must match
    the visible-chip count or libtpu rejects/ignores the extra chips, and
    must fit inside the host's chip grid (x is the narrow dimension: v5e-8 /
    v6e-8 hosts are a 2x4 grid, so 8 chips is '2,4,1', never '4,2,1')."""
    host = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")
    if host:
        try:
            hx, hy, hz = (int(v) for v in host.split(","))
            if hx * hy * hz == n:  # all chips: mirror the host grid exactly
                return host
        except ValueError:
            pass
    grids = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1", 16: "4,4,1"}
    return grids.get(n, "1,{},1".format(n))
