"""The readers of the program's own spans, counters and named scopes
(``layer_metrics/_program.py`` and the files that use it): each on a
hand-made ``run`` record, the phase reduction on hand-made device events and
on a small recorded CPU trace."""

import importlib.util
import json
import os
import sysconfig

import pytest

from benchmarks import run as bench_run
from benchmarks.layer_metrics import _program

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FWD = "jit(tos_train_step)/tos.loss_and_grad/jvp()/dot_general"
BWD = "jit(tos_train_step)/tos.loss_and_grad/transpose(jvp(tos.loss_and_grad))/jvp()/checkpoint/dot_general"
REMAT = "jit(tos_train_step)/tos.loss_and_grad/transpose(jvp(tos.loss_and_grad))/jvp()/checkpoint/rematted_computation/tanh"
OPT = "jit(tos_train_step)/tos.optimizer/mul"


def _run(counters=None, gauges=None, seconds=10.0):
    return {"workload": "lm1024.packed4k", "window": {"seconds": seconds, "counters": counters or {},
                                                       "gauges": gauges or {}}, "trace": None}


COUNTED = {
    "h2d_place_seconds_total": 1.5, "data_consumer_wait_seconds_total": 0.25, "train_step_stall_seconds_total": 0.0,
    "train_step_dispatch_seconds_total": 0.06, "train_steps_dispatched_total": 12.0,
}
GAUGED = {"node_backend_start_seconds": 11.2, "compile_cache_load_seconds": 20.5}


@pytest.mark.parametrize("name,value", [
    ("lm_h2d_place_pct", 15.0), ("img_h2d_place_pct", 15.0), ("lm_batch_wait_pct", 2.5), ("img_batch_wait_pct", 2.5),
    ("lm_step_stall_pct", 0.0), ("img_step_stall_pct", 0.0), ("lm_dispatch_ms_per_step", 5.0),
    ("img_dispatch_ms_per_step", 5.0), ("backend_start_s", 11.2), ("cache_load_s", 20.5),
])
def test_reader_on_a_hand_made_run(name, value):
    read = bench_run.reader("per_layer", name)
    assert read(_run(COUNTED, GAUGED)) == pytest.approx(value)
    # a program that does not count (the parent): nothing to read, nothing raised
    assert read(_run()) is None


@pytest.mark.parametrize("name", ["lm_fwd_pct", "lm_recompute_pct", "lm_bwd_pct", "img_fwd_pct", "img_bwd_pct"])
def test_phase_reader_without_a_trace_reads_nothing(name):
    assert bench_run.reader("per_layer", name)(_run(COUNTED, GAUGED)) is None
    # a traced run whose trace is not on disk
    assert bench_run.reader("per_layer", name)(dict(_run(), workload="no.such-cell", trace={"busy_s": 1.0})) is None


def test_phase_of_an_op_name():
    assert [_program.phase_of(n) for n in (FWD, BWD, REMAT, OPT, "jit(tos_train_step)/add", "")] == [
        "fwd", "bwd", "recompute", "opt", "other", "other"]
    # a fused operation carries several names: the first rule that matches decides
    assert _program.phase_of(FWD + ";" + REMAT) == "recompute"


def test_phase_shares_on_two_chips_by_hand():
    ops = [(FWD, 0.0, 2.0), (REMAT, 2.0, 3.0), (BWD, 3.0, 7.0), (OPT, 7.0, 7.5), ("", 7.5, 8.0), (FWD, 20.0, 30.0)]
    shares = _program.phase_shares({"/device:TPU:0": ops, "/device:TPU:1": ops}, window=(0.0, 10.0))
    assert shares == {"fwd": 25.0, "recompute": 12.5, "bwd": 50.0, "opt": 6.25, "other": 6.25}
    assert sum(shares.values()) == 100.0
    # no operation under a tos. scope (the parent's step): nothing to read
    bare = [("jit(train_step)/jvp(jit(loss))/dot_general", 0.0, 1.0)]
    assert _program.phase_shares({"/device:TPU:0": bare}) is None


def test_phase_shares_of_the_recorded_cpu_trace():
    """Two steps of a two-block checkpointed loss under AdamW, recorded on the
    CPU backend (``bench.dispatch`` / ``bench.fence`` around each): CPU events
    carry only ``hlo_op``, so the ``op_name`` table comes from the compiled
    step's text, kept beside the trace."""
    with open(os.path.join(DATA, "cpu_two_scope.op_names.json")) as f:
        op_names = json.load(f)
    devices, window = _program.load_device_ops(os.path.join(DATA, "cpu_two_scope.xplane.pb"), op_names)
    assert window is not None and len(devices) == 1
    shares = _program.phase_shares(devices, window)
    assert all(shares[phase] > 0 for phase in ("fwd", "recompute", "bwd", "opt"))
    # every executed instruction is attributed: the rest is the three the table does not name
    assert 95.0 < sum(shares[p] for p in ("fwd", "recompute", "bwd", "opt")) <= 100.0 + 1e-9
    # without the table the CPU trace names no scope
    assert _program.phase_shares(*_program.load_device_ops(os.path.join(DATA, "cpu_two_scope.xplane.pb"))) is None


def test_sampled_v5e_trace():
    """Two traced ResNet-50 steps on one v5e chip (my chip run, PR 24), cut to
    every 24th device operation, the ``bench.*`` / ``tos.*`` host events and
    the stats the readers use: the layout a TPU's profiler really writes. An
    operation's ``tf_op`` sits in its event metadata (a reference to a stat's
    name), which ``jax.profiler.ProfileData`` does not show."""
    path = os.path.join(DATA, "v5e_resnet50_sampled.xplane.pb")
    planes = _program.read_planes(path)
    device = planes["/device:TPU:0"]
    assert len(device["Steps"]) == 2 and len(device["XLA Ops"]) == 300
    named = [st["tf_op"] for _, _, _, st in device["XLA Ops"] if st.get("tf_op")]
    assert len(named) > 20 and all(n.startswith("jit(tos_train_step)/tos.") for n in named)  # the rest: copies
    assert all(st.get("hlo_category") for _, _, _, st in device["XLA Ops"])
    # the program's span lies inside the benchmark's, on one clock, and the
    # device's operations follow the first dispatch
    host = planes["/host:CPU"]["python3"]
    dispatch = [(s, e) for name, s, e, _ in host if name == "bench.dispatch"]
    inner = [(s, e) for name, s, e, _ in host if name == "tos.step_dispatch"]
    assert len(dispatch) == len(inner) == 2
    assert all(lo <= s and e <= hi for (lo, hi), (s, e) in zip(dispatch, inner))
    assert min(s for _, s, _, _ in device["XLA Ops"]) > inner[0][0]
    devices, window = _program.load_device_ops(path)
    shares = _program.phase_shares(devices, window)
    assert shares["fwd"] > 0 and shares["bwd"] > shares["fwd"] and shares["recompute"] == 0
    assert sum(shares.values()) == pytest.approx(100.0)


def _installed_xplane_pb2():
    """The generated ``xplane_pb2`` that the installation's tensorflow ships,
    loaded from its file (it needs protobuf alone, not tensorflow)."""
    path = os.path.join(sysconfig.get_paths()["purelib"], "tensorflow", "tsl", "profiler", "protobuf",
                        "xplane_pb2.py")
    if not os.path.exists(path):
        pytest.skip("no generated xplane_pb2 in this installation")
    spec = importlib.util.spec_from_file_location("_installed_xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_schema_is_the_one_the_installation_ships():
    """Every message ``read_planes`` walks, field for field: a renumbered or
    added field in ``xplane.proto`` fails here, not in a metric."""
    pb2 = _installed_xplane_pb2()
    for message, fields in _program.SCHEMA.items():
        if message != "MapEntry":
            assert {f.number: f.name for f in getattr(pb2, message).DESCRIPTOR.fields} == fields, message


def test_read_planes_agrees_with_the_generated_parser():
    """The recorded v5e sample through both readers: the same planes, lines,
    events, times and string stats (an event's own over its metadata's)."""
    pb2 = _installed_xplane_pb2()
    path = os.path.join(DATA, "v5e_resnet50_sampled.xplane.pb")
    with open(path, "rb") as f:
        space = pb2.XSpace.FromString(f.read())
    ours = _program.read_planes(path)
    assert set(ours) == {p.name for p in space.planes if p.lines}
    theirs = {}
    for plane in space.planes:
        def strings(stats):
            out = {}
            for stat in stats:
                kind = stat.WhichOneof("value")
                if kind == "str_value":
                    out[plane.stat_metadata[stat.metadata_id].name] = stat.str_value
                elif kind == "ref_value":
                    out[plane.stat_metadata[stat.metadata_id].name] = plane.stat_metadata[stat.ref_value].name
            return out

        for line in plane.lines:  # lines of one name (a process's threads) are read as one
            rows = theirs.setdefault(plane.name, {}).setdefault(line.name, [])
            for event in line.events:
                meta = plane.event_metadata[event.metadata_id]
                start = line.timestamp_ns * 1e-9 + event.offset_ps * 1e-12
                rows.append((meta.name, start, start + event.duration_ps * 1e-12,
                             dict(strings(meta.stats), **strings(event.stats))))
    assert ours == theirs
    assert sum(len(rows) for lines in ours.values() for rows in lines.values()) > 300


@pytest.mark.parametrize("raw,complaint", [
    (bytes([9 << 3 | 0, 1]), "field 9 in an XSpace"),          # a varint field xplane.proto does not have
    (bytes([1 << 3 | 2, 2, 7 << 3 | 0, 1]), "field 7 in an XPlane"),  # the same, one message down
    (bytes([1 << 3 | 3]), "wire type 3"),                         # a group: not in proto3
])
def test_reader_stops_at_what_the_schema_lacks(tmp_path, raw, complaint):
    path = tmp_path / "odd.xplane.pb"
    path.write_bytes(raw)
    with pytest.raises(ValueError, match=complaint):
        _program.read_planes(str(path))
