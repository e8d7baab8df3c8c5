"""The trace reduction on a small synthetic trace."""
import pytest

from benchlib import trace


def test_op_name_strips_instruction_suffix():
    assert trace.op_name("%rfc_encode.19 = (f32[8,128]) custom-call(x)") \
        == "rfc_encode"
    assert trace.op_name("%cavity_tconv_step.29 = f32[8] custom-call()") \
        == "cavity_tconv_step"
    assert trace.op_name("%fusion = f32[2] fusion(a)") == "fusion"


def test_union_merges_overlaps():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]


def _events():
    # window [1, 11] s; device 0 busy 2-4 (two overlapping ops), 5-6, 8-10
    ops = {0: [("graph_sconv", 2.0, 3.0), ("cavity_tconv_step", 2.5, 4.0),
               ("rfc_encode", 5.0, 6.0), ("cavity_tconv", 8.0, 10.0),
               ("fusion", 0.0, 0.5)]}                 # before the window
    modules = {0: [("jit_slab_step", 2.0, 4.0), ("jit_slab_step", 4.5, 6.0),
                   ("jit_slab_step", 7.5, 10.5)]}
    host = [("bench.trace_start", 1.0, 1.0), ("bench.trace_end", 11.0, 11.0),
            ("bench.tick", 1.0, 6.5), ("bench.readback", 4.0, 5.0),
            ("bench.wait", 6.5, 8.0)]
    return ops, modules, host


def test_busy_idle_families_and_steps():
    red = trace.reduce_events(*_events())
    assert red["window_s"] == pytest.approx(10.0)
    assert red["busy_s"] == pytest.approx(2.0 + 1.0 + 2.0)
    assert red["family_s"]["sconv"] == pytest.approx(1.0)
    assert red["family_s"]["tconv"] == pytest.approx(1.5 + 2.0)
    assert red["family_s"]["rfc"] == pytest.approx(1.0)
    assert "fusion" not in red["op_s"]
    assert red["step_busy_s"] == pytest.approx([2.0, 1.0, 2.0])


def test_idle_gaps_named_by_the_innermost_host_span():
    red = trace.reduce_events(*_events())
    gaps = sorted((round(d, 6), n) for d, n in red["gaps"])
    # 1-2 tick, 4-5 readback (inside tick), 6-8 mostly wait (mid 7.0),
    # 10-11 nothing
    assert gaps == [(1.0, "bench.readback"), (1.0, "bench.tick"),
                    (1.0, "none"), (2.0, "bench.wait")]
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] == "cavity_tconv"
    assert dict(bd["idle_gaps"])["bench.wait"] == pytest.approx(2.0)


def test_no_markers_reads_nothing():
    ops, modules, host = _events()
    assert trace.reduce_events(ops, modules, host[2:]) == {}
