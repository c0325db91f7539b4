"""(c) The trace reduction: its interval arithmetic, and the recorded
trace kept beside this file."""

import os

import pytest

import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb")


def test_union_counts_overlapping_operations_once():
    assert xplane.union_seconds([]) == 0.0
    assert xplane.union_seconds([(0, 1e9), (0.5e9, 2e9), (3e9, 4e9)]) == pytest.approx(3.0)
    assert xplane.union_seconds([(5e9, 6e9), (0, 1e9)]) == pytest.approx(2.0)
    assert xplane.union_seconds([(0, 4e9), (1e9, 2e9)]) == pytest.approx(4.0)


def test_longest_gaps_lie_between_the_first_and_the_last_operation():
    intervals = [(0, 1e9), (1.5e9, 2e9), (1.8e9, 3e9), (7e9, 8e9)]
    assert xplane.longest_gaps(intervals) == [(3e9, 4e9), (1e9, 0.5e9)]
    assert xplane.longest_gaps(intervals, keep=1) == [(3e9, 4e9)]
    assert xplane.longest_gaps([]) == []
    # with the trace's own bounds, the idle before and after counts too
    assert xplane.longest_gaps([(2e9, 3e9)], window=(0.0, 10e9)) == [(3e9, 7e9), (0.0, 2e9)]


@pytest.mark.parametrize(
    "event, module",
    [("jit_fit(1234567890123)", "jit_fit"), ("jit_fit", "jit_fit"),
     ("jit__lambda_(42) ", "jit__lambda_")],
)
def test_module_name_drops_the_fingerprint(event, module):
    assert xplane.module_name(event) == module


def test_device_planes_are_the_chips():
    assert xplane.DEVICE_PLANE.match("/device:TPU:0")
    assert xplane.DEVICE_PLANE.match("/device:TPU:3").group(1) == "3"
    assert not xplane.DEVICE_PLANE.match("/host:CPU")
    assert not xplane.DEVICE_PLANE.match("/device:TPU:0 SparseCore")


#: a two-chip trace written out by hand in the profiler's own schema
#: (XSpace as text; times of a line are picoseconds from the line's
#: timestamp_ns), shaped as a v5e's: one plane a chip with the lines
#: ``XLA Modules`` and ``XLA Ops``, operations named by their HLO text
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000000 }
    events { metadata_id: 1 offset_ps: 4000000000 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 6000000000 duration_ps: 500000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 4 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 3 offset_ps: 4000000000 duration_ps: 1000000000 }
    events { metadata_id: 5 offset_ps: 6000000000 duration_ps: 500000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_fit(123456789)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_predict(42)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p0), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4 name: "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %fusion.1), replica_groups={}" } }
  event_metadata { key: 5 value { id: 5 name: "%custom-call.3 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p0), custom_call_target=\\"tpu_custom_call\\"" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p0), kind=kLoop" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000
      stats { metadata_id: 1 int64_value: 1700000000000001000 } } }
  event_metadata { key: 1 value { id: 1 name: "chipbench_clock" } }
  stat_metadata { key: 1 value { id: 1 name: "wall_ns" } }
}
"""


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("trace") / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    return str(path)


def test_reduction_of_a_known_trace(synthetic):
    reduced = xplane.reduce(synthetic)
    first, second = reduced["devices"]
    assert [d["name"] for d in reduced["devices"]] == ["/device:TPU:0", "/device:TPU:1"]
    # operations overlap from 1 ms to 2 ms: busy counts that once
    assert first["busy_s"] == pytest.approx(4.5e-3)
    assert first["op_seconds"] == pytest.approx(5.5e-3)
    assert first["events"] == 4 and first["distinct_ops"] == 3
    assert first["modules"] == {
        "jit_fit": {"seconds": pytest.approx(4e-3), "count": 2},
        "jit_predict": {"seconds": pytest.approx(5e-4), "count": 1},
    }
    assert first["ops"][0] == [
        "%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p0), kind=kLoop",
        pytest.approx(3e-3), 2,
    ]
    assert first["kernel_seconds"] == pytest.approx(5e-4)
    assert first["collective_seconds"] == pytest.approx(2e-3)
    # idle from 3 ms to 4 ms and from 5 ms to 6 ms, the longer first
    assert [g[1] for g in first["gaps"]] == [pytest.approx(1e6), pytest.approx(1e6)]
    assert second["busy_s"] == pytest.approx(1e-3)
    assert reduced["busy_s"] == pytest.approx((4.5e-3 + 1e-3) / 2)
    # no environment plane: the window is first operation to last, and
    # the clock comes from the benchmark's annotation
    assert reduced["window_s"] == pytest.approx(6.5e-3)
    assert reduced["profile_start_wall_ns"] == pytest.approx(1700000000000001000 - 1000, abs=2)
    assert xplane.reduce(synthetic, chips=1)["busy_s"] == pytest.approx(4.5e-3)


def test_readers_read_the_known_trace(synthetic):
    from harness import manifest

    trace = xplane.reduce(synthetic)
    evidence = {"trace": dict(trace, window_s=9e-3)}

    def read(name):
        return manifest.load_module(manifest.ROOT, "layer_metrics", name).read(evidence)

    assert read("device_idle_pct") == pytest.approx(100 * (1 - 2.75e-3 / 9e-3))
    assert read("chip_imbalance_pct") == pytest.approx(100 * 3.5 / 4.5)
    assert read("collective_time_pct") == pytest.approx(100 * 2e-3 / 5.5e-3)
    assert read("kernel_busy_share_pct") == pytest.approx(100 * 5e-4 / 2.75e-3)


def test_categories_and_short_names():
    kernel = '%custom-call.7 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call"'
    assert xplane.category(kernel) == "kernel"
    assert xplane.short_name(kernel) == kernel[:80] and xplane.short_name("plain") == "plain"
    assert xplane.category("%all-gather.1 = f32[8]{0} all-gather(%x)") == "collective"
    assert xplane.category("%all-reduce-start = (f32[8]) all-reduce-start(%x)") == "collective"
    assert xplane.category("%fusion.9 = f32[8]{0} fusion(%all-reduce.1), kind=kLoop") == ""


def test_reduction_of_the_recorded_v5e_trace():
    """One small trace recorded on a v5e chip (PR 23): three runs of a
    jitted 512 x 512 matmul-tanh-sum, a pause, two runs of a Pallas
    kernel that adds two arrays, under ``procs/common.Trace``."""
    reduced = xplane.reduce(RECORDED)
    assert len(reduced["devices"]) == 1
    device = reduced["devices"][0]
    assert device["name"] == "/device:TPU:0"
    assert device["events"] == 11 and device["distinct_ops"] == 4
    assert reduced["window_s"] == pytest.approx(0.319815481)
    assert reduced["busy_s"] == device["busy_s"] == pytest.approx(1.7443e-05)
    assert device["modules"] == {
        "jit_matmul_tanh": {"seconds": pytest.approx(7.511e-06), "count": 3},
        "jit_kernel_add": {"seconds": pytest.approx(9.961e-06), "count": 2},
    }
    # the Pallas kernel is a custom call whose HLO text names tpu_custom_call
    assert device["kernel_seconds"] == pytest.approx(9.952e-06)
    assert device["ops"][0][0].startswith("%kernel_add.1 = f32[512,512]")
    assert device["ops"][0][2] == 2 and device["collective_seconds"] == 0.0
    # the longest idle stretch is the tail, then the pause, then the head
    assert [round(g[1] / 1e6) for g in device["gaps"][:3]] == [225, 52, 41]
    assert reduced["profile_start_wall_ns"] == 1790452393605385908
    idle = 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
    assert idle == pytest.approx(99.99455, abs=1e-4)
