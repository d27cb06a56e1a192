"""The trace reducer on a synthetic trace whose numbers are worked out by
hand, and on a real ``ProfileData`` of this machine for the extraction."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402

# window [1000, 11000]. Device: x [0,1200] (clipped to [1000,1200]),
# fusion.1 [1500,3500], custom-call.2 [3000,4000] (overlaps), fusion.1
# [6000,9000]. Union 200 + 2500 + 3000 = 5700 of 10000.
SYNTHETIC = {
    "devices": {
        "/device:TPU:0": [("x", 0, 1200), ("fusion.1", 1500, 2000),
                          ("custom-call.2", 3000, 1000),
                          ("fusion.1", 6000, 3000)],
        "/device:TPU:1": [],
    },
    "host": [("bench_window", 1000, 10000), ("dispatch", 1100, 500),
             ("fetch_loss", 3900, 2200), ("drain", 8000, 3000),
             ("dispatch", 9100, 100)],
}


def test_busy_idle_ops_and_gaps():
    s = trace_reduce.summarize(SYNTHETIC, chips=1)
    assert s["planes"] == ["/device:TPU:0"]
    assert s["window_s"] == pytest.approx(10000e-9)
    assert s["busy_s"] == pytest.approx(5700e-9)
    assert s["op_seconds"] == pytest.approx(
        {"x": 200e-9, "fusion.1": 5000e-9, "custom-call.2": 1000e-9})
    assert s["op_calls"] == {"x": 1, "fusion.1": 2, "custom-call.2": 1}
    assert s["device_ops"][0] == ["fusion", pytest.approx(5000e-9)]
    # gaps [1200,1500] under dispatch, [4000,6000] under fetch_loss,
    # [9000,11000] under drain (the dispatch inside it covers 100 only)
    assert sorted(s["idle_gaps"]) == sorted(
        [["drain", pytest.approx(2000e-9)],
         ["fetch_loss", pytest.approx(2000e-9)],
         ["dispatch", pytest.approx(300e-9)]])
    assert s["idle_by_span"] == pytest.approx(
        {"drain": 2000e-9, "fetch_loss": 2000e-9, "dispatch": 300e-9})
    assert s["busy_s"] + sum(s["idle_by_span"].values()) == pytest.approx(
        s["window_s"])


def test_parse_op_reads_name_and_kind_from_hlo_text():
    pallas = ('%jvp__.12 = bf16[32,12,512,64]{3,2,1,0:T(8,128)(2,1)S(1)} '
              'custom-call(s32[1]{0:T(128)} %constant.375, bf16[32,12,512,64]'
              '{3,2,1,0} %bitcast.2446), custom_call_target="tpu_custom_call"'
              ', operand_layout_constraints={s32[1]{0}}')
    assert trace_reduce.parse_op(pallas) == (
        "jvp__.12", "custom-call:tpu_custom_call")
    fusion = ('%convert_reduce_fusion.54 = (f32[32,512]{1,0:T(8,128)S(1)}, '
              'bf16[32,512,768]{2,1,0}) fusion(bf16[768]{0} %copy-done.1229, '
              'f32[3072,768]{1,0} %custom-call.213), kind=kOutput, '
              'calls=%fused_computation.1')
    assert trace_reduce.parse_op(fusion) == (
        "convert_reduce_fusion.54", "fusion")
    assert trace_reduce.parse_op("%copy.3 = f32[8]{0} copy(f32[8]{0} %p)") \
        == ("copy.3", "copy")
    assert trace_reduce.parse_op("dot_general.1") == ("dot_general.1", "")
    assert trace_reduce.base_name("convert_reduce_fusion.54") == \
        "convert_reduce_fusion"
    assert trace_reduce.base_name("jvp__.12") == "jvp__"


def test_device_ops_are_grouped_without_their_numbers():
    data = {"devices": {"/device:TPU:0": [("fusion.1", 0, 10),
                                          ("fusion.22", 10, 30),
                                          ("copy.3", 40, 5)]},
            "host": [("bench_window", 0, 100)],
            "kinds": {"fusion.1": "fusion", "fusion.22": "fusion",
                      "copy.3": "copy"}}
    s = trace_reduce.summarize(data, chips=1)
    assert s["device_ops"] == [["fusion", pytest.approx(40e-9)],
                               ["copy", pytest.approx(5e-9)]]
    assert s["op_kinds"] == data["kinds"]
    assert s["kind_seconds"] == pytest.approx({"fusion": 40e-9,
                                               "copy": 5e-9})


def test_gap_outside_any_span_is_named_host_other():
    data = {"devices": {"/device:TPU:0": [("a", 10, 10), ("a", 50, 10)]},
            "host": [("bench_window", 0, 100)]}
    s = trace_reduce.summarize(data, chips=1)
    assert {name for name, _ in s["idle_gaps"]} == {"host_other"}
    assert s["busy_s"] == pytest.approx(20e-9)


def test_two_chips_average():
    data = {"devices": {"/device:TPU:0": [("a", 0, 40)],
                        "/device:TPU:1": [("a", 0, 20)]},
            "host": [("bench_window", 0, 100)]}
    s = trace_reduce.summarize(data, chips=2)
    assert s["busy_s"] == pytest.approx(30e-9)
    assert s["op_seconds"]["a"] == pytest.approx(30e-9)


def test_empty_device_is_an_error():
    data = {"devices": {"/device:TPU:0": []},
            "host": [("bench_window", 0, 100)]}
    with pytest.raises(ValueError):
        trace_reduce.summarize(data, chips=1)


def test_extract_reads_a_real_profile(tmp_path):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench_window"):
        with TraceAnnotation("dispatch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    data = trace_reduce.extract(trace_reduce.load(str(tmp_path)),
                                ("bench_window", "dispatch"))
    names = [e[0] for e in data["host"]]
    assert names.count("bench_window") == 1 and "dispatch" in names
    win = next(e for e in data["host"] if e[0] == "bench_window")
    dis = next(e for e in data["host"] if e[0] == "dispatch")
    assert win[1] <= dis[1] and dis[1] + dis[2] <= win[1] + win[2]
