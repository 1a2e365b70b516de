"""The program's names in a trace: innermost device scope of an op_name,
kernel names from operation names, the scope and kernel reduction on an
xspace with op_names from its program's HLO text, and the recorded trace's
readings, which reading the program's names leaves as they were."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, scopes, trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "trace_small.xplane.pb"
SECURE = ('%fl_secure_commit.1 = f32[8,256]{1,0} custom-call(f32[8,8,256]{2,1,0}'
          ' %c), custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("op_name,scope", [
    ("jit(round_sequential)/while/body/fl.local_train/while/body/"
     "transpose(jvp(xlstm))/dot_general", "fl.local_train"),
    ("jit(f)/transpose(jvp(fl.local_train))/mul", "fl.local_train"),
    ("jit(commit)/fl.commit/fl.commit/jit(fused_secure_commit_tree)/"
     "fl.commit.pack/concatenate", "fl.commit.pack"),
    ("jit(commit)/fl.commit/jit(fused_secure_commit_tree)/fl_secure_commit/"
     "pallas_call", "fl.commit"),
    ("jit(commit)/fl.server_step/add", "fl.server_step"),
    ("jit(commit)/add", "(none)"),
    ("jit(f)/self.fl.x/add", "(none)"),
    (None, "(none)"),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


@pytest.mark.parametrize("op,kernel", [
    (SECURE, "fl_secure_commit"),
    ('%f.1 = f32[8]{0} custom-call(f32[8]{0} %a), '
     'custom_call_target="tpu_custom_call"', "f"),
    ("%fusion.3 = f32[4] fusion(f32[4] %b), kind=kLoop", None),
    ("fusion.3", None),
])
def test_kernel_of(op, kernel):
    assert scopes.kernel_of(op) == kernel


def test_kernel_seconds_from_ops():
    ops = {SECURE: 0.04, SECURE.replace(".1 =", ".2 ="): 0.01,
           "%fusion.3 = f32[4] fusion(f32[4] %b)": 0.5}
    assert scopes.kernel_seconds(ops) == {
        "fl_secure_commit": pytest.approx(0.05)}


def test_hlo_op_names():
    text = """
ENTRY %main {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(commit)/fl.commit.pack/reshape" source_file="x.py"}
  ROOT %fl_quantize.2 = f32[4]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(commit)/fl.commit/fl_quantize/pallas_call"}
}"""
    assert scopes.hlo_op_names(text) == {
        "fusion.1": "jit(commit)/fl.commit.pack/reshape",
        "fl_quantize.2": "jit(commit)/fl.commit/fl_quantize/pallas_call"}


XSPACE = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 250000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 3500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = bf16[8] fusion(bf16[8] %a)" } }
  event_metadata { key: 2 value { id: 2 name: "%fl_quantize.1 = f32[8] custom-call(f32[8] %b), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%add.3 = bf16[8] add(bf16[8] %c, bf16[8] %d)" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.4 = f32[8] copy(f32[8] %e)" } }
  event_metadata { key: 5 value { id: 5 name: "%while.5 = (s32[]) while((s32[]) %w)" } }
}
planes {
  name: "/host:CPU"
  lines {
    name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 8000000 }
    events { metadata_id: 3 offset_ps: 3500000 duration_ps: 2500000 }
    events { metadata_id: 4 offset_ps: 700000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "fl.round" } }
  event_metadata { key: 3 value { id: 3 name: "fl.round.fetch" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction" } }
}
"""
# the compiled program the xspace's operations come from: their op_names
HLO = """
  %fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop, calls=%c, metadata={op_name="jit(r)/fl.local_train/dot"}
  %fl_quantize.1 = f32[8]{0} custom-call(f32[8]{0} %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(r)/fl.commit/fl_quantize/pallas_call"}
  %add.3 = bf16[8]{0} add(bf16[8]{0} %c, bf16[8]{0} %d), metadata={op_name="jit(r)/fl.server_step/add"}
  %copy.4 = f32[8]{0} copy(f32[8]{0} %e)
"""


def test_reduce_scopes_and_kernels_from_xspace():
    import jax
    pd = jax.profiler.ProfileData.from_text_proto(XSPACE)
    ops, spans, modules = scopes.read_profile(pd)
    assert [n for n, _, _ in spans] == ["chipbench.traced", "fl.round",
                                        "fl.round.fetch"]
    op_names = scopes.hlo_op_names(HLO)
    window = [(s, e) for n, s, e in spans if n == trace.WINDOW_SPAN][0]
    inner = [x for x in spans if x[0] != trace.WINDOW_SPAN]
    r = scopes.reduce(ops, inner, window, op_names, modules=modules)
    # the loop holds the first three operations and is not counted again
    assert r["scopes"] == pytest.approx({
        "fl.local_train": 2e-6, "fl.commit": 2e-6,
        "fl.server_step": 0.5e-6, "(none)": 0.25e-6})
    assert r["kernels"] == {"fl_quantize": [pytest.approx(2e-6), 2]}
    # idle gaps take the program's innermost span
    assert sorted(n for n, _ in r["gaps"]) == [
        "(none)", "fl.round", "fl.round", "fl.round.fetch"]
    # and the keys trace.reduce gives read as it reads them
    base = trace.reduce(ops, [x for x in inner if x[0].startswith(
        trace.SPAN_PREFIX)], window, 10, modules)
    for k in ("busy_s", "window_s", "ops", "modules"):
        assert r[k] == base[k]
    assert r["breakdown"]["device_ops"] == base["breakdown"]["device_ops"]


def test_recorded_trace_readings_pinned():
    """The readings of the trace recorded on a TPU v5e, as the benchmark's
    reduction has given them since it was recorded."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(RECORDED))
    ops, spans, modules = trace.read_profile(pd)
    window = [(s, e) for n, s, e in spans if n == trace.WINDOW_SPAN][0]
    spans = [x for x in spans if x[0] != trace.WINDOW_SPAN]
    r = trace.reduce(ops, spans, window, 10, modules)
    assert r["busy_s"] == pytest.approx(0.008298447, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.407047048, rel=1e-9)
    assert len(r["ops"]) == 60
    assert sum(r["ops"].values()) == pytest.approx(0.008298447, rel=1e-9)
    assert r["modules"] == {}
    top = r["breakdown"]["device_ops"]
    assert [n for n, _ in top[:3]] == ["reshape.223 reshape",
                                       "reshape.231 reshape",
                                       "reshape.226 reshape"]
    assert top[0][1] == pytest.approx(0.002916201, rel=1e-9)
    assert len(r["gaps"]) == 51
    assert r["breakdown"]["idle_gaps"][0] == [
        "chipbench.fetch", pytest.approx(0.396658486, rel=1e-9)]
    # reading the program's names as well changes none of it
    ops, spans, modules = scopes.read_profile(pd)
    ext = scopes.reduce(ops, [x for x in spans if x[0] != trace.WINDOW_SPAN],
                        window, {}, 10, modules)
    for k in ("busy_s", "window_s", "ops", "modules", "gaps", "breakdown"):
        assert ext[k] == r[k], k
    assert ext["scopes"] == pytest.approx({"(none)": r["busy_s"]})


def test_commit_kernel_ms_reads_the_named_kernel():
    cell = harness.Cell("xlstm-125m.commit-secure8-k8")
    read = cell.readers()["commit_kernel_ms"].read
    x = {"trace": {"ops": {SECURE: 0.88}}, "traced_commits": 20}
    assert read(x) == pytest.approx(44.0)
    # a program whose kernel has no stable name reads nothing
    old = SECURE.replace("%fl_secure_commit.1", "%f.1")
    assert read({"trace": {"ops": {old: 0.88}}, "traced_commits": 20}) is None
