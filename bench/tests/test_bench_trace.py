"""The reduction of a profiler trace: busy union, idle share, kernel time
by name, the longest gaps by host span, and the roofline readers."""

import os
import types

import pytest
from jax.profiler import ProfileData

from harness import flops, manifest, trace

# Device ops (us, on the trace clock): a kernel 1000-3000, a fusion
# 2000-5000 overlapping it, a copy 7000-8000 inside a loop of the same
# extent; host spans: the traced window
# 1000-11000, a round inside it 1000-9000, and a sleep 5000-7000.
SYNTHETIC = r'''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 3000000000 }
    events { metadata_id: 3 offset_ps: 6000000000 duration_ps: 1000000000 }
    events { metadata_id: 5 offset_ps: 6000000000 duration_ps: 1000000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 7000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%kan_fused_gemm_pallas.3 = bf16[256,1024]{1,0:T(8,128)(2,1)} custom-call(bf16[256,512]{1,0:T(8,128)(2,1)} %x, f32[512,8,1024]{2,1,0:T(8,128)} %c, f32[512,1024]{1,0:T(8,128)} %w), custom_call_target=\"tpu_custom_call\", operand_layout_constraints={bf16[256,512]{1,0}, f32[512,8,1024]{2,1,0}, f32[512,1024]{1,0}}" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.3 = bf16[16,8,64]{2,1,0:T(8,128)(2,1)} fusion(bf16[16,3120,8,64]{3,2,1,0} %a), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.1 = bf16[4,256]{1,0} copy(bf16[4,256]{0,1} %b)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step(123)" } }
  event_metadata { key: 5 value { id: 5 name: "%while.2 = (s32[], bf16[4,256]{1,0}) while((s32[], bf16[4,256]{1,0}) %t), condition=%c, body=%b" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 8000000000 }
    events { metadata_id: 3 offset_ps: 4000000000 duration_ps: 2000000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.round" } }
  event_metadata { key: 3 value { id: 3 name: "time.sleep" } }
}
'''


@pytest.fixture
def synthetic():
    return trace.reduce(ProfileData.from_text_proto(SYNTHETIC))


def test_busy_union_and_window(synthetic):
    assert synthetic.window == (1e6, 11e6)
    assert synthetic.busy_intervals(0) == [(1e6, 5e6), (7e6, 8e6)]
    assert synthetic.busy_s() == pytest.approx(5e-3)
    assert synthetic.window_s == pytest.approx(10e-3)


def test_idle_share(synthetic):
    reader = manifest.load_module(manifest.metric_path("idle_share"), "idle")
    run = types.SimpleNamespace(trace=synthetic)
    assert reader.read(run) == pytest.approx(50.0)


def test_ops_by_instruction(synthetic):
    (ev,) = synthetic.ops(lambda e: e.op.split(".")[0] == "kan_fused_gemm_pallas")
    assert ev.dur_ns == 2e6 and ev.opcode == "custom-call"
    assert [e.opcode for e in synthetic.device_ops[0]] == [
        "custom-call", "fusion", "copy", "while"]
    # the loop is left out: its time is the copy's
    assert synthetic.top_ops(3) == [
        ["%fusion.3 = bf16[16,8,64]", pytest.approx(3e-3)],
        ["%kan_fused_gemm_pallas.3 = bf16[256,1024]", pytest.approx(2e-3)],
        ["%copy.1 = bf16[4,256]", pytest.approx(1e-3)]]


def test_gaps_by_host_span(synthetic):
    # idle: 5-7 ms (the sleep) and 8-11 ms (the window's tail)
    assert synthetic.idle_gaps() == [["bench.traced", pytest.approx(3e-3)],
                                     ["time.sleep", pytest.approx(2e-3)]]


def test_kernel_roofline_reader(synthetic):
    peaks = flops.peaks("TPU v5 lite")
    reader = manifest.load_module(manifest.metric_path("kan_roofline"), "kan")
    run = types.SimpleNamespace(trace=synthetic, peaks=peaks)
    # bound by bytes at 256 rows: x, coefficients, base weights, result
    nbytes = 256 * 512 * 2 + 512 * 8 * 1024 * 4 + 512 * 1024 * 4 + 256 * 1024 * 2
    assert nbytes / 819e9 > 2 * 256 * 512 * 9 * 1024 / 197e12
    assert reader.read(run) == pytest.approx(100 * nbytes / 819e9 / 2e-3)
    gather = manifest.load_module(manifest.metric_path("gather_roofline"), "gat")
    assert gather.read(run) is None              # no gather in this trace


RECORDED = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


def test_recorded_trace():
    """A trace recorded on one v5e chip: two rounds, each a fused KAN layer
    at 256 rows and a paged gather, then 20 ms of host sleep."""
    tr = trace.load(RECORDED)
    assert 0 < tr.busy_s() < tr.window_s
    assert tr.top_ops(1)[0][0].startswith("%kan_fused_gemm_pallas")
    span, gap = tr.idle_gaps(1)[0]
    assert span == "$time sleep" and 0.015 < gap < tr.window_s
    run = types.SimpleNamespace(trace=tr, peaks=flops.peaks("TPU v5 lite"))
    read = {name: manifest.load_module(manifest.metric_path(name), f"rec_{name}").read(run)
            for name in ("kan_roofline", "gather_roofline", "idle_share")}
    # 256 rows: bound by the coefficients' bytes, 19.66 MB, in 77.8 us
    assert read["kan_roofline"] == pytest.approx(100 * 19_660_800 / 819e9 / 77.8e-6, rel=0.01)
    assert 0 < read["idle_share"] < 100
    # a 1 MB gather: its event (2.29 us) ends before all of its 2 MB of
    # transfers could have, so the share is over 100 at this size; at the
    # engine's sizes (200 MB and more a call) it reads about a fifth
    assert read["gather_roofline"] > 0
