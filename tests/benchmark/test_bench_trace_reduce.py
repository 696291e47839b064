"""The reduction from a profiler trace to numbers: on a hand-made plane with
known intervals, and on a small trace recorded on the v5e chip in PR 23."""

import os

import jax
import pytest

import metriclib
import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HAND_MADE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 11 offset_ps: 6000000 duration_ps: 3000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 6500000 duration_ps: 1000000 } }
  lines { id: 3 name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 6 offset_ps: 7000000 duration_ps: 1500000 } }
  event_metadata { key: 6 value { id: 6 name: "%all-gather-start.3 = (f32[2]{0}, f32[8]{0}) all-gather-start(f32[2]{0} %p)" } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%attn.7 = bf16[8]{0} custom-call(bf16[8]{0} %q)" } }
  event_metadata { key: 3 value { id: 3 name: "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %g)" } }
  event_metadata { key: 4 value { id: 4 name: "%while.5 = (s32[]) while((s32[]) %t)" } }
  event_metadata { key: 5 value { id: 5 name: "%attn.9 = bf16[8]{0} custom-call(bf16[8]{0} %q)" } }
  event_metadata { key: 10 value { id: 10 name: "jit_step(123)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_ragged_prefill(456)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5500000 }
    events { metadata_id: 2 offset_ps: 5500000 duration_ps: 4500000 }
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench/step" } }
  event_metadata { key: 2 value { id: 2 name: "bench/submit" } }
  event_metadata { key: 3 value { id: 3 name: "$engine.py:1184 step" } }
}
"""


def test_names():
    assert T.base_name("fusion.123") == "fusion"
    assert T.base_name("jit_step(4567)") == "jit_step"
    assert T.base_name("%attn.9 = bf16[8,32]{1,0} custom-call(s32[32]{0} %x.1)") == "attn"
    assert T.base_name("%all-gather-start.3 = (f32[4]) all-gather-start(f32[2] %p)") == "all-gather-start"


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (10, 1)]) == [[0, 4], [10, 11]]
    assert T.total(T.union([(0, 2), (1, 3), (10, 1)])) == 5
    assert T.subtract([[0, 10]], [[2, 3], [5, 7]]) == [[0, 2], [3, 5], [7, 10]]
    assert T.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert T.subtract([[0, 4]], []) == [[0, 4]]


def test_hand_made_plane():
    """Window 1000..11000 ns from the host spans. The while is a container:
    the device is busy 0-5 us and, inside it, 6.5-7.5 us: 6 of 10 us. The
    all-reduce (3-5 us) overlaps attn.7 for 1 us, the asynchronous all-gather
    (7-8.5 us) overlaps attn.9 for 0.5 us: 3.5 us of collectives, 2 us
    exposed. The kernel inside jit_step is attn.7 (2 us); attn.9 ran inside
    the prefill program. Idle 5-6.5 us began under bench/step, 7.5-10 us
    under bench/submit."""
    data = jax.profiler.ProfileData.from_text_proto(HAND_MADE)
    trace = T.read_planes(data.planes)
    assert [s[0] for s in trace["spans"]] == ["bench/step", "bench/submit"]
    r = T.reduce(trace)
    dev = r["devices"][0]
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == dev["busy_s"] == pytest.approx(6e-6)
    assert dev["collective_s"] == pytest.approx(3.5e-6)
    assert dev["collective_exposed_s"] == pytest.approx(2e-6)
    assert "while" not in dev["op_s"]
    assert dev["module_s"] == {"jit_step": pytest.approx(4e-6), "jit_ragged_prefill": pytest.approx(3e-6)}
    assert dev["op_s"]["attn"] == pytest.approx(3e-6)
    assert T.ops_inside(trace, 0, "jit_step", "^attn$") == pytest.approx(2e-6)
    assert T.ops_inside(trace, 0, "jit_ragged_prefill", "^attn$") == pytest.approx(1e-6)
    assert r["idle_gaps"] == [["bench/submit", pytest.approx(2.5e-6)], ["bench/step", pytest.approx(1.5e-6)]]
    both = {"reduced": r, "raw": trace}
    assert metriclib.device_idle_pct(both) == pytest.approx(40.0)
    assert metriclib.decode_step_device_ms(both) == pytest.approx(4e-3)
    assert metriclib.prefill_device_share_pct(both) == pytest.approx(50.0)
    assert metriclib.collective_exposed_pct(both) == pytest.approx(20.0)
    # 2 us of decode kernel for 819e9 B/s * 1e-6 s of page-rounded cache: 50% of the memory bound
    counters = {"traced": {"decode_kv_bytes": 819000}}
    cell = {"peaks": {"hbm_bytes_per_s": 819e9}}
    assert metriclib.decode_attn_roofline_pct(both, counters, cell) == pytest.approx(50.0)
    names = [n for n, _ in T.breakdown(r)["device_ops"]]
    assert names[:2] == ["program:jit_step", "program:jit_ragged_prefill"] and "while" not in names
    assert metriclib.op_share_pct(both, metriclib.FLASH_KERNEL) == pytest.approx(50.0)  # 3 of 6 us


def test_trace_recorded_on_the_chip():
    """Two scheduler iterations of the batch serving cell on one TPU v5e
    (chip call 1 of PR 23, seed 101; cut to the device's two XLA lines and
    the bench/ spans): each iteration one ragged prefill dispatch and one
    decode step. The numbers are this reduction's reading of that file."""
    trace = T.load(os.path.join(DATA, "v5e_batch_two_iterations.xplane.pb"))
    r = T.reduce(trace)
    dev = r["devices"][0]
    assert r["span_names"] == ["bench/emit", "bench/step", "bench/submit"]
    assert r["window_s"] == pytest.approx(1.499750424, rel=1e-9)
    assert r["busy_s"] == pytest.approx(1.451386232, rel=1e-9)
    assert dev["module_s"]["jit_ragged_prefill"] == pytest.approx(0.810611211, rel=1e-9)
    assert dev["module_s"]["jit_step"] == pytest.approx(0.640710393, rel=1e-9)
    assert len(dev["module_durations_s"]["jit_step"]) == 2
    assert dev["op_s"]["attn"] == pytest.approx(1.235612397, rel=1e-9)
    assert T.ops_inside(trace, 0, "jit_step", "^attn$") == pytest.approx(0.526091072, rel=1e-9)
    assert T.ops_inside(trace, 0, "jit_ragged_prefill", "^attn$") == pytest.approx(0.709521325, rel=1e-9)
    assert dev["collective_s"] == dev["collective_exposed_s"] == 0.0
    assert r["idle_gaps"][0] == ["bench/step", pytest.approx(0.046788853, rel=1e-9)]
    both = {"reduced": r, "raw": trace}
    assert metriclib.device_idle_pct(both) == pytest.approx(3.224816024456078)
    assert metriclib.decode_step_device_ms(both) == pytest.approx(320.3551965)
    assert metriclib.prefill_device_share_pct(both) == pytest.approx(55.85082682526094)
    assert T.breakdown(r)["device_ops"][0] == ["program:jit_ragged_prefill", pytest.approx(0.810611211)]
