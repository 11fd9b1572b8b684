"""The trace reduction on synthesized device events: busy union, window
clipping, kernel time by stable name, idle gaps charged to host spans."""

import pytest

from perfbench import trace

CHUNK = ("%_chunk_cvs_pallas_fast.1 = u32[8,202,8,128]{3,2,1,0:T(8,128)S(1)} "
         "custom-call(u32[202,1024,256]{2,1,0:T(8,128)} %reshape.13), "
         "custom_call_target=\"tpu_custom_call\"")
COPY = ("%copy.18 = u32[2048,57,256]{2,1,0:T(8,128)} "
        "copy(u32[2048,57,256]{2,0,1:T(8,128)} %words.1)")


def test_op_names_are_stable():
    assert trace.op_name(CHUNK) == "_chunk_cvs_pallas_fast"
    assert trace.op_name(COPY) == "copy"
    assert trace.op_name("%fusion = u32[4] fusion()") == "fusion"


def test_busy_kernels_and_gaps():
    ops = {"/device:TPU:0": [(0, 10, CHUNK), (5, 10, COPY), (30, 10, CHUNK),
                             (95, 20, COPY), (200, 5, CHUNK)]}
    spans = [("round", 0, 100), ("job/file_identifier", 20, 60)]
    red = trace.reduce_ops(ops, 0, 100, spans)
    assert red["busy_s"] == pytest.approx(30e-9)      # [0,15] [30,40] [95,100]
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["kernel_s"]["_chunk_cvs_pallas_fast"] == pytest.approx(20e-9)
    assert red["kernel_s"]["copy"] == pytest.approx(15e-9)
    # gap [15,30]: round to 20, then the job; gap [40,95]: the job to 60,
    # then round
    assert red["idle_s"] == pytest.approx(
        {"job/file_identifier": 30e-9, "round": 40e-9})


def test_busy_averages_over_chips():
    ops = {"/device:TPU:0": [(0, 50, COPY)], "/device:TPU:1": [(0, 10, COPY)]}
    red = trace.reduce_ops(ops, 0, 100)
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["devices"] == 2
    assert red["idle_s"] == pytest.approx({"no span": 50e-9})


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_ops({}, 0, 100)


def test_recorded_v5e_trace():
    """A trace recorded on one v5e chip (tests/data/v5e_probe.xplane.pb,
    cut down to its ops line and the benchmark's annotations): the
    CAS programs for 2,048 sampled and 2,048 whole small files, a
    batched checksum page and one streamed 6 MiB checksum, inside a
    `perfbench.work` annotation of 1,512,246,384 ns."""
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_probe.xplane.pb")
    ops, marks = trace.read_xplane(path)
    assert list(ops) == ["/device:TPU:0"] and len(ops["/device:TPU:0"]) == 2809
    w0 = marks["perfbench.work"][0]
    red = trace.reduce_ops(ops, w0, w0 + 1_512_246_384,
                           [("work", w0, w0 + 1_512_246_384)])
    # three Pallas chunk-kernel calls: 1,212,036 + 769,015 + 684,560 ns
    assert red["kernel_s"]["_chunk_cvs_pallas_fast"] == pytest.approx(
        2.665611e-3)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["idle_s"]["work"] == pytest.approx(
        red["window_s"] - red["busy_s"])
