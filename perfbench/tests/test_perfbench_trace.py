"""The reduction from trace events to busy time, kernel time, top
operations and named idle gaps, on a small recorded trace."""
import pytest

from perfbench import trace


def _trace():
    # one device: a layer loop holding a kernel and a matmul, a gap while
    # the host pumps, a prefill, then a gap while the generator waits
    dev = [
        (100, 400, "%while.2 = (s32[], bf16[8,1,64]) while(...)"),
        (110, 300, "%paged_decode_attention.5 = bf16[96,1,128]{2,1} "
                   "custom-call(s32[8,16]{1,0} %a)"),
        (300, 390, "%fusion.1 = bf16[8,64]{1,0} fusion(bf16[64,64]{1,0})"),
        (600, 700, "%fusion.9 = bf16[1,212,64]{2,1,0} fusion(%x)"),
    ]
    host = [
        (0, 1000, "window"),
        (60, 550, "pump"),
        (90, 420, "cloud_decode_rows"),
        (560, 720, "submit"),
        (580, 710, "cloud_prefix"),
        (730, 1000, "generator_wait"),
    ]
    return {"devices": [dev], "host": host}


def test_busy_is_the_union_of_operations():
    red = trace.reduce(_trace(), 0, 1000, {"paged_decode": "paged_decode"})
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(400e-9)


def test_self_time_takes_nested_ops_out_of_their_loop():
    red = trace.reduce(_trace(), 0, 1000, {"paged_decode": "paged_decode"})
    ops = dict(red["device_ops"])
    assert ops["%paged_decode_attention.5 = bf16[96,1,128]"] == \
        pytest.approx(190e-9)
    assert ops["%while.2 = tuple"] == \
        pytest.approx(20e-9)
    assert sum(ops.values()) == pytest.approx(red["busy_s"])
    assert red["kernel_s"]["paged_decode"] == pytest.approx(190e-9)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    red = trace.reduce(_trace(), 0, 1000, {})
    gaps = dict(red["idle_gaps"])
    assert gaps["outside_spans"] == pytest.approx(100e-9)     # 0..100
    assert gaps["pump"] == pytest.approx(200e-9)              # 400..600
    assert gaps["generator_wait"] == pytest.approx(300e-9)    # 700..1000
    assert sum(gaps.values()) == pytest.approx(600e-9)


def test_window_clips_operations():
    red = trace.reduce(_trace(), 200, 650, {"paged_decode": "paged_decode"})
    assert red["busy_s"] == pytest.approx((400 - 200 + 50) * 1e-9)
    assert red["kernel_s"]["paged_decode"] == pytest.approx(100e-9)


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"devices": [], "host": []}, 0, 1, {})


def test_short_names():
    assert trace.short("%fusion.64 = bf16[8,200064]{1,0:T(8,128)} "
                       "fusion(bf16[3072,200064]{1,0} %p)") == \
        "%fusion.64 = bf16[8,200064]"


def test_a_one_chip_cell_on_a_four_chip_host_reads_its_own_plane():
    """Four TPU planes, the other three idle or busy with other work: a
    cell on chip 0 reads plane 0's busy time, not the average."""
    mine = _trace()["devices"][0]
    planes = {"/device:TPU:0": mine,
              "/device:TPU:1": [],
              "/device:TPU:2": [(0, 1000, "%fusion.3 = f32[8]")],
              "/device:TPU:3": []}
    own = {"devices": trace.own_planes(planes, [0]),
           "host": _trace()["host"]}
    red = trace.reduce(own, 0, 1000, {"paged_decode": "paged_decode"})
    assert red["busy_s"] == pytest.approx(400e-9)
    assert red["kernel_s"]["paged_decode"] == pytest.approx(190e-9)
    assert len(trace.own_planes(planes, [0, 1, 2, 3])) == 4
    assert trace.own_planes(planes, None) == list(planes.values())
    with pytest.raises(ValueError, match="4"):
        trace.own_planes(planes, [4])
