"""The trace reduction: device busy time as a union of intervals, idle gaps
named by the host span around them, on synthetic events and on a small
trace recorded on a TPU v5e and trimmed."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "trace_small.xplane.pb"


@pytest.mark.parametrize("intervals,want", [
    ([(0, 10), (5, 15)], [(0, 15)]),                    # overlap
    ([(0, 100), (10, 20), (30, 40)], [(0, 100)]),       # nesting
    ([(30, 40), (0, 10)], [(0, 10), (30, 40)]),         # disjoint, unsorted
    ([(0, 10), (10, 20)], [(0, 20)]),                   # touching
    ([(5, 5), (7, 6)], []),                             # empty intervals
    ([], []),
])
def test_union(intervals, want):
    assert trace.union(intervals) == want


def test_reduce_busy_idle_and_gap_names():
    ops = {"/device:TPU:0": [("fusion.1", 100, 300), ("fusion.2", 200, 400),
                             ("custom-call.3", 600, 700),
                             ("fusion.1", 900, 1200)]}
    spans = [("chipbench.commit", 60, 650), ("chipbench.fetch", 650, 1000)]
    r = trace.reduce(ops, spans, (0, 1000))
    assert r["window_s"] == pytest.approx(1e-6)
    # busy: [100,400) + [600,700) + [900,1000) = 500 ns
    assert r["busy_s"] == pytest.approx(500e-9)
    assert r["ops"]["fusion.1"] == pytest.approx(300e-9)   # clipped at 1000
    assert r["ops"]["fusion.2"] == pytest.approx(200e-9)
    gaps = sorted(r["gaps"], key=lambda g: g[1])
    # [0,100) before any span, [400,600) in commit, [700,900) in fetch
    assert [n for n, _ in gaps] == ["(none)", "chipbench.commit",
                                    "chipbench.fetch"]
    assert dict(r["gaps"])["chipbench.fetch"] == pytest.approx(200e-9)
    assert r["breakdown"]["device_ops"][0][0] == "fusion.1"
    assert len(r["breakdown"]["idle_gaps"]) == 3


def test_reduce_innermost_span_and_device_average():
    ops = {"/device:TPU:0": [("a", 0, 50)],
           "/device:TPU:1": [("a", 0, 100)]}
    spans = [("chipbench.run_round", 0, 100), ("chipbench.fetch", 80, 90)]
    r = trace.reduce(ops, spans, (0, 100))
    assert r["busy_s"] == pytest.approx(75e-9)           # mean of 50 and 100
    assert r["ops"]["a"] == pytest.approx(75e-9)
    assert r["gaps"] == [("chipbench.run_round", pytest.approx(50e-9))]


@pytest.mark.parametrize("op,short,code", [
    ('%f.1 = f32[8,256]{1,0:T(8,128)} custom-call(f32[8,8,256]{2,1,0} %c),'
     ' custom_call_target="tpu_custom_call"', "f.1 custom-call tpu_custom_call",
     "custom-call"),
    ("%copy-start.2 = (bf16[8]{0:T(8)(2,1)S(1)}, u32[]{:S(2)}) copy-start("
     "bf16[8]{0} %p)", "copy-start.2 copy-start", "copy-start"),
    ("%while.3 = (s32[], f32[4]) while((s32[], f32[4]) %t)", "while.3 while",
     "while"),
    ("jit_commit(123)", "jit_commit(123)", None),
])
def test_short_names(op, short, code):
    assert trace.short_name(op) == short
    assert trace.opcode(op) == code


def test_breakdown_lists_ops_not_loops():
    ops = {"/device:TPU:0": [
        ("%while.1 = (s32[]) while((s32[]) %a)", 0, 100),
        ("%fusion.2 = f32[4] fusion(f32[4] %b)", 10, 60),
        ("%fusion.3 = f32[4] fusion(f32[4] %b)", 60, 90)]}
    r = trace.reduce(ops, [], (0, 100))
    assert r["busy_s"] == pytest.approx(100e-9)
    assert [n for n, _ in r["breakdown"]["device_ops"]] == [
        "fusion.2 fusion", "fusion.3 fusion"]


def test_reduce_empty_window():
    r = trace.reduce({"/device:TPU:0": [("a", 0, 10)]}, [], (20, 20))
    assert r["busy_s"] == 0.0 and r["window_s"] == 0.0 and r["gaps"] == []


SYNTHETIC = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
  }
  lines {
    name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_commit" } }
}
planes {
  name: "/host:CPU"
  lines {
    name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 600000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.fetch" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction" } }
}
"""


def test_read_profile_from_xspace():
    import jax
    pd = jax.profiler.ProfileData.from_text_proto(SYNTHETIC)
    ops, spans, modules = trace.read_profile(pd)
    assert list(ops) == ["/device:TPU:0"]
    assert [n for n, _, _ in ops["/device:TPU:0"]] == ["fusion.7",
                                                       "custom-call.2"]
    assert [n for n, _, _ in spans] == ["chipbench.traced",
                                        "chipbench.fetch"]
    window = [(s, e) for n, s, e in spans if n == trace.WINDOW_SPAN][0]
    r = trace.reduce(ops, spans[1:], window)
    assert r["window_s"] == pytest.approx(9e-6)
    assert r["busy_s"] == pytest.approx(3e-6)


def test_recorded_trace():
    """A few commits of xlstm-125m.commit-secure8-k8 traced on one TPU v5e,
    trimmed to the first device ops and the benchmark's host spans."""
    ops, spans, modules = trace.read_xplane(RECORDED)
    assert list(ops) == ["/device:TPU:0"] and len(ops["/device:TPU:0"]) > 10
    window = [(s, e) for n, s, e in spans if n == trace.WINDOW_SPAN]
    assert len(window) == 1
    r = trace.reduce(ops, [x for x in spans if x[0] != trace.WINDOW_SPAN],
                     window[0])
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["breakdown"]["device_ops"]
    assert all(n.startswith("chipbench.") or n == "(none)"
               for n, _ in r["gaps"])
