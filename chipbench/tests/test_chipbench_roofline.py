"""Shapes to bytes, and the kernels' least bytes, on given shapes."""
from __future__ import annotations

import pytest

from chipbench import bench
from chipbench import trace as T

PROBE = ("%merge_probe_pallas.1 = (s32[4096]{0:T(1024)}, s32[4096]{0:T(1024)}) "
         "custom-call(%a, %b, %c, %d, %e, /*index=5*/%f), "
         'custom_call_target="tpu_custom_call", operand_layout_constraints='
         "{s32[4]{0}, s32[4]{0}, s32[4096]{0}, s32[4096]{0}, s32[65536]{0}, "
         "s32[65536]{0}}, frontend_attributes={kernel_metadata={}}")
TILED = ("%segment_reduce_pallas.1 = s32[17408]{0:T(1024)S(1)} custom-call(%a, "
         '%b, %c, %d), custom_call_target="tpu_custom_call", '
         "operand_layout_constraints={s32[16]{0}, s32[16]{0}, s32[16384]{0}, "
         "s32[16384]{0}}, frontend_attributes={kernel_metadata={}}")
RESIDENT = ("%segment_reduce_pallas.2 = s32[8192]{0} custom-call(%a, %b), "
            'custom_call_target="tpu_custom_call", operand_layout_constraints='
            "{s32[16384]{0}, s32[16384]{0}}, frontend_attributes={}")


def reader(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py")


def op(text, dur_ns=1000.0):
    return T.Op(text.split(" ", 1)[0].lstrip("%"), 0.0, dur_ns,
                {"long_name": text})


@pytest.mark.parametrize("text,want", [
    ("s32[4096]{0}", [16384]),
    ("(s64[8,2]{1,0}, pred[3])", [128, 3]),
    ("bf16[2,3,4] f32[] u8[7]", [48, 4, 7]),
])
def test_shape_bytes(text, want):
    assert T.shape_bytes(text) == want


def test_custom_call_bytes_reads_results_and_operands():
    assert T.custom_call_bytes(op(PROBE)) == (
        [16384, 16384], [16, 16, 16384, 16384, 262144, 262144])
    assert T.custom_call_bytes(op("%sort.3 = s32[8] sort(%x)")) is None


def test_probe_least_bytes():
    # probe and build key words once, both rank outputs once; the two
    # block-bound vectors are left out
    res, opnds = T.custom_call_bytes(op(PROBE))
    assert reader("merge_probe_roofline").min_bytes(res, opnds) == (
        2 * 16384 + 2 * 16384 + 2 * 262144)


@pytest.mark.parametrize("text,want", [
    (TILED, 17408 * 4 + 2 * 16384 * 4),
    (RESIDENT, 8192 * 4 + 2 * 16384 * 4),
])
def test_segment_reduce_least_bytes(text, want):
    res, opnds = T.custom_call_bytes(op(text))
    assert reader("segment_reduce_roofline").min_bytes(res, opnds) == want


def test_roofline_share_from_a_trace():
    # two calls of 589824 least bytes need 1440.3 ns at 819 GB/s; the
    # kernel took 8402 ns
    ops = [op(PROBE, 4201.0), op(PROBE, 4201.0)]
    for i, o in enumerate(ops):
        o.start_ns = 10.0 + 5000 * i
    tr = T.Trace(devices={0: ops}, spans=[("window", 0.0, 20000.0)])
    run = type("Run", (), {"trace": tr, "device_kind": "TPU v5 lite"})
    got = reader("merge_probe_roofline").read(run)
    assert got == pytest.approx(100 * 2 * 589824 / 819e9 / 8402e-9)
    with pytest.raises(T.NothingToRead, match="segment_reduce"):
        reader("segment_reduce_roofline").read(run)


def test_unknown_device_kind_is_an_error():
    from chipbench.peaks import peaks
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
