"""The trace reduction on a small recorded trace, numbers checked by hand.

The rows have the shape ``harness.trace.events`` gives: (plane, line,
event, start_ns, dur_ns). They follow a v5e trace's layout: a device
plane with a module line and an op line nested in it, a host plane with
the clock mark and the program's threads.
"""

from pathlib import Path

import pytest

from harness import trace

DEV = "/device:TPU:0"
HOST = "/host:CPU"
ROWS = [
    # module executions: [100, 400) and [600, 700)
    (DEV, "XLA Modules", "jit_h264_band_step(17)", 100, 300),
    (DEV, "XLA Modules", "jit_h264_row_damage_probe(5)", 600, 100),
    # ops nested inside the first module, one overlapping another
    (DEV, "XLA Ops", "fusion.1", 100, 150),
    (DEV, "XLA Ops", "fusion.2", 200, 150),
    (DEV, "XLA Ops", "copy.3", 360, 40),
    (DEV, "XLA Ops", "reduce.4", 600, 100),
    # a step-marker line spans idle time: not work
    (DEV, "Steps", "step 7", 0, 1000),
    # host events never count as device time
    (HOST, "python", trace.CLOCK_MARK, 50, 1),
    (HOST, "python", "PjitFunction", 0, 1000),
]


def test_busy_is_the_union_over_nested_lines():
    r = trace.reduce(ROWS, 0, 1000)
    # [100, 400) and [600, 700): 300 + 100, whatever nests inside
    assert r["busy_ns"] == 400
    assert r["window_ns"] == 1000
    assert r["devices"] == 1


def test_ops_are_counted_once_from_the_op_line():
    r = trace.reduce(ROWS, 0, 1000)
    assert dict(r["ops"]) == {"fusion.1": 150, "fusion.2": 150,
                              "copy.3": 40, "reduce.4": 100}
    assert r["ops"][-1] == ("copy.3", 40)


def test_programs_come_from_the_module_line_with_short_names():
    r = trace.reduce(ROWS, 0, 1000)
    assert dict(r["programs"]) == {"jit_h264_band_step": 300,
                                   "jit_h264_row_damage_probe": 100}
    assert trace.short_name(
        "%fusion.2127 = u32[3133440]{0:T(1024)S(1)} fusion(s32[7]{0} %a)") \
        == "fusion.2127 u32[3133440]"
    assert trace.short_name("jit_h264_i_step(3521667335159303676)") \
        == "jit_h264_i_step"


def test_gaps_longest_first_and_clipped_to_the_window():
    r = trace.reduce(ROWS, 0, 1000)
    assert r["gaps"] == [(700, 1000), (400, 600), (0, 100)]
    clipped = trace.reduce(ROWS, 150, 650)
    assert clipped["busy_ns"] == (400 - 150) + (650 - 600)
    assert clipped["gaps"] == [(400, 600)]


def test_a_trace_without_a_device_lane_fails():
    with pytest.raises(trace.NoDeviceLane):
        trace.reduce([r for r in ROWS if r[0] == HOST])


def test_busy_is_averaged_over_devices():
    rows = ROWS + [("/device:TPU:1", "XLA Ops", "fusion.9", 0, 1000)]
    assert trace.reduce(rows, 0, 1000)["busy_ns"] == (400 + 1000) / 2


def test_gap_labels_come_from_host_spans_on_the_trace_clock():
    offset = trace.clock_offset(ROWS, mark_host_ns=10_050)
    assert offset == 10_000
    spans = [("encode.dispatch", 10_400, 10_520),
             ("packetize", 10_450, 10_900)]
    assert trace.label_gap((400, 600), spans, offset) \
        == "encode.dispatch+packetize"
    assert trace.label_gap((0, 100), spans, offset) == "no span open"
    assert trace.label_gap((0, 100), spans, None) == "unattributed"


def test_a_recorded_v5e_trace():
    """``data/v5e_matmul.xplane.pb``: one v5e chip, five runs of a jitted
    2048x2048 matmul-and-sum 20 ms apart (my chip run, PR 22). Each run is
    one module on ``XLA Modules`` with one fusion nested in it on
    ``XLA Ops``; the host's threads are dropped."""
    rows = trace.events(str(Path(__file__).parent / "data"
                            / "v5e_matmul.xplane.pb"))
    assert {r[:2] for r in rows} == {(DEV, "XLA Modules"), (DEV, "XLA Ops")}
    r = trace.reduce(rows)
    # the five module durations, read off the trace by hand
    assert r["busy_ns"] == 190270 + 190067 + 190043 + 190226 + 190065
    assert r["programs"] == [("jit__lambda", 950671)]
    assert r["ops"] == [("fusion.2 f32[2048]", 190266 + 190064 + 190040
                         + 190222 + 190062)]
    # first module start to last module end
    assert r["window_ns"] == 142672022 + 190065 - 56309604
    assert len(r["gaps"]) == 4
