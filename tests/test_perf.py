"""Performance observability plane (ISSUE 6): occupancy/critical-path
math on synthetic timelines, the perf ledger's record/check round-trip
(incl. CPU-vs-TPU key isolation and noise-band edges), the static cost
registry + roofline math, the profiler-capture parser, and the
AOT-instrumented step wrapper.
"""

import gzip
import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from selkies_tpu.obs import perf as perf_mod  # noqa: E402
from selkies_tpu.trace.export import (timelines_from_events,  # noqa: E402
                                      to_trace_events)
from selkies_tpu.trace.summary import (BUBBLE,  # noqa: E402
                                       frame_critical_path, lane_occupancy,
                                       occupancy_report, render_occupancy)
from tools import perf_ledger  # noqa: E402

MS = 1_000_000  # ns


def _tl(frame_id, t0, t1, spans, display="d0"):
    return {"display_id": display, "frame_id": frame_id,
            "t0_ns": t0, "t1_ns": t1,
            "spans": [{"name": n, "lane": la, "t0_ns": s0, "dur_ns": d}
                      for n, la, s0, d in spans]}


# ------------------------------------------------------- occupancy math
def test_serial_pipeline_zero_overlap_critical_path_equals_stage_sum():
    """Fully-serial pipeline: overlap fraction == 0 and the critical
    path IS the stage sum (each stage's charge == its duration)."""
    tl = _tl(1, 0, 30 * MS, [
        ("capture", "cap", 0, 10 * MS),
        ("encode.dispatch", "main", 10 * MS, 12 * MS),
        ("packetize", "main", 22 * MS, 8 * MS),
    ])
    cp = frame_critical_path(tl)
    assert cp["overlap_fraction"] == 0.0
    assert cp["bubble_ms"] == 0.0
    assert cp["stages"] == {"capture": 10.0, "encode.dispatch": 12.0,
                            "packetize": 8.0}
    assert cp["e2e_ms"] == cp["stage_sum_ms"] == 30.0


def test_serial_pipeline_gap_becomes_bubble():
    tl = _tl(1, 0, 30 * MS, [
        ("capture", "cap", 0, 10 * MS),
        # 5 ms of nothing: host stall the spans never covered
        ("packetize", "main", 15 * MS, 15 * MS),
    ])
    cp = frame_critical_path(tl)
    assert cp["bubble_ms"] == 5.0
    assert cp["stages"]["capture"] == 10.0
    assert cp["stages"]["packetize"] == 15.0
    # accounting is exact: stages + bubble == e2e
    assert sum(cp["stages"].values()) + cp["bubble_ms"] == cp["e2e_ms"]


def test_overlapped_timeline_attributes_gating_stage():
    """Constructed overlap: a=[0,10], b=[2,12] in a 12 ms frame. The
    shared [2,10] window is charged to b (it ends later — it is what
    gates completion), so a keeps only its solo [0,2]."""
    tl = _tl(1, 0, 12 * MS, [
        ("a", "l1", 0, 10 * MS),
        ("b", "l2", 2 * MS, 10 * MS),
    ])
    cp = frame_critical_path(tl)
    assert cp["stages"] == {"a": 2.0, "b": 10.0}
    # union 12 of 20 summed span-ms -> 40% overlap
    assert cp["overlap_fraction"] == pytest.approx(0.4)
    assert cp["bubble_ms"] == 0.0
    assert sum(cp["stages"].values()) == cp["e2e_ms"]


def test_open_or_empty_frames_are_skipped():
    assert frame_critical_path(
        _tl(1, 0, None, [("a", "l", 0, MS)])) is None
    assert frame_critical_path(_tl(1, 0, 10 * MS, [])) is None
    rep = occupancy_report([_tl(1, 0, None, [("a", "l", 0, MS)])])
    assert rep["frames"] == 0


def test_occupancy_report_aggregates_and_renders():
    tls = [
        _tl(1, 0, 10 * MS, [("capture", "cap", 0, 4 * MS),
                            ("encode.dispatch", "main", 4 * MS, 6 * MS)]),
        _tl(2, 20 * MS, 32 * MS, [
            ("capture", "cap", 20 * MS, 4 * MS),
            ("encode.dispatch", "main", 24 * MS, 8 * MS)]),
    ]
    rep = occupancy_report(tls)
    assert rep["frames"] == 2
    assert rep["overlap_fraction"] == 0.0
    # capture: 8 of 22 total e2e ms; dispatch: 14 of 22
    assert rep["critical_path"]["encode.dispatch"]["share"] == \
        pytest.approx(14 / 22, abs=1e-4)
    assert rep["critical_path"]["capture"]["share"] == \
        pytest.approx(8 / 22, abs=1e-4)
    assert rep["e2e_ms"]["p50"] in (10.0, 12.0)
    text = render_occupancy(rep)
    assert "encode.dispatch" in text and "overlap=0.0%" in text


def test_occupancy_report_reads_waits_as_no_work():
    """A wait span (on ``<lane>.wait``) is neither a busy lane nor a
    critical-path stage: the report reads as if it were not there, so a
    wait outside every work span stays bubble."""
    work = [("capture", "cap", 0, 4 * MS),
            ("encode.dispatch", "cap", 4 * MS, 6 * MS),
            ("packetize", "slot0", 14 * MS, 6 * MS)]
    waits = [("encode.probe_sync", "cap.wait", 5 * MS, 4 * MS),
             ("pipeline.queue", "slot0.wait", 10 * MS, 4 * MS),
             ("relay.queue", "ws.wait", 20 * MS, 5 * MS)]
    plain = occupancy_report([_tl(1, 0, 25 * MS, work)])
    rep = occupancy_report([_tl(1, 0, 25 * MS, work + waits)])
    assert rep == plain
    assert set(rep["lanes"]) == {"cap", "slot0"}
    assert set(rep["critical_path"]) == {"capture", "encode.dispatch",
                                         "packetize"}
    # [10, 14] and [20, 25] hold only waits: 9 of 25 ms
    assert rep["bubble_share"] == pytest.approx(9 / 25)


def test_lane_occupancy_detects_bubbles():
    """Two frames pipelined on two lanes: the cap lane works [0,4] and
    [10,14] inside a [0,20] window -> 40% occupancy, 6 ms worst gap."""
    tls = [
        _tl(1, 0, 12 * MS, [("capture", "cap", 0, 4 * MS),
                            ("encode", "dev", 4 * MS, 8 * MS)]),
        _tl(2, 10 * MS, 20 * MS, [("capture", "cap", 10 * MS, 4 * MS),
                                  ("encode", "dev", 14 * MS, 6 * MS)]),
    ]
    lanes = lane_occupancy(tls)
    assert lanes["cap"]["busy_ms"] == 8.0
    assert lanes["cap"]["window_ms"] == 20.0
    assert lanes["cap"]["occupancy"] == pytest.approx(0.4)
    assert lanes["cap"]["largest_gap_ms"] == 6.0
    # the dev lane is busy [4,12]+[14,20]: 14/20, worst gap 4 (start)
    assert lanes["dev"]["occupancy"] == pytest.approx(0.7)
    assert lanes["dev"]["largest_gap_ms"] == 4.0


def test_lane_occupancy_clips_spans_to_window():
    """A span adopted by frame-id that outlives its frame envelope (the
    relay ws.send pattern) is clipped to the window: busy can never
    exceed the denominator, occupancy never reads > 100%."""
    tls = [
        _tl(1, 0, 10 * MS, [
            ("encode", "dev", 0, 10 * MS),
            # ws.send attached to frame 1 but running [5, 25] — 15 ms
            # of it lies beyond the frame window's w1 of 10 ms
            ("ws.send", "relay", 5 * MS, 20 * MS),
        ]),
    ]
    lanes = lane_occupancy(tls)
    assert lanes["relay"]["busy_ms"] == 5.0
    assert lanes["relay"]["window_ms"] == 10.0
    assert lanes["relay"]["occupancy"] == pytest.approx(0.5)
    for lane in lanes.values():
        assert lane["busy_ms"] <= lane["window_ms"]
        assert lane["occupancy"] <= 1.0


def test_occupancy_survives_export_roundtrip():
    """A saved /api/trace snapshot must occupancy-analyze identically
    to the live ring (timelines_from_events inverts to_trace_events)."""
    tls = [_tl(7, 0, 12 * MS, [("a", "l1", 0, 10 * MS),
                               ("b", "l2", 2 * MS, 10 * MS)])]
    doc = to_trace_events(tls)
    back = timelines_from_events(doc["traceEvents"])
    assert len(back) == 1
    assert back[0]["frame_id"] == 7
    direct = occupancy_report(tls)
    via_export = occupancy_report(back)
    assert via_export["overlap_fraction"] == \
        pytest.approx(direct["overlap_fraction"])
    assert via_export["critical_path"].keys() == \
        direct["critical_path"].keys()


# ------------------------------------------------------------ perf ledger
def _bench_doc(fps=10.0, p99=80.0, backend="cpu", status="ok",
               metric="encode_fps_256x128_h264_tpu"):
    return {"metric": metric, "value": fps, "unit": "fps",
            "vs_baseline": round(fps / 60.0, 3),
            "latency_p50_ms": p99 * 0.6, "latency_p99_ms": p99,
            "backend": backend,
            "backend_health": {"status": status, "reason": "test"},
            "stages_ms": {"encode.dispatch": 9.0, "packetize": 1.0}}


def _record(ledger, doc, host=None):
    entry = perf_ledger.entry_from_bench(doc, host=host)
    perf_ledger.append_entry(str(ledger), entry)
    return entry


def test_ledger_record_check_roundtrip(tmp_path):
    led = tmp_path / "ledger.jsonl"
    _record(led, _bench_doc())
    _record(led, _bench_doc(fps=9.8, p99=82.0))
    entries = perf_ledger.read_ledger(str(led))
    assert len(entries) == 2
    assert all(e["baseline_eligible"] for e in entries)
    assert entries[0]["resolution"] == "256x128"
    assert entries[0]["codec"] == "h264"
    # within-band drift: check passes
    assert perf_ledger.main(["--ledger", str(led), "check"]) == 0


def test_ledger_check_fails_on_seeded_regression(tmp_path):
    """The ISSUE acceptance fixture: record a healthy baseline, then a
    seeded regression — check must fail (and pass with --warn-only)."""
    led = tmp_path / "ledger.jsonl"
    _record(led, _bench_doc(fps=10.0, p99=80.0))
    _record(led, _bench_doc(fps=6.0, p99=200.0))
    assert perf_ledger.main(["--ledger", str(led), "check"]) == 1
    assert perf_ledger.main(
        ["--ledger", str(led), "check", "--warn-only"]) == 0


def test_ledger_noise_band_edges(tmp_path):
    led = tmp_path / "ledger.jsonl"
    base = perf_ledger.entry_from_bench(_bench_doc(fps=10.0, p99=100.0))
    # exactly on the band edge: NOT a regression (band is exclusive)
    at_edge = perf_ledger.entry_from_bench(_bench_doc(fps=8.5, p99=115.0))
    assert perf_ledger.compare(at_edge, base, band=0.15) == []
    beyond_fps = perf_ledger.entry_from_bench(
        _bench_doc(fps=8.49, p99=100.0))
    assert len(perf_ledger.compare(beyond_fps, base, band=0.15)) == 1
    beyond_p99 = perf_ledger.entry_from_bench(
        _bench_doc(fps=10.0, p99=115.1))
    assert len(perf_ledger.compare(beyond_p99, base, band=0.15)) == 1
    # a tighter band flags the edge case too
    assert len(perf_ledger.compare(at_edge, base, band=0.10)) == 2


def test_ledger_failed_cpu_run_never_compares_against_tpu(tmp_path):
    """The r4/r5 rule, structurally: a cpu run with a failed backend
    verdict (asked for a tpu, got the cpu) has backend class 'cpu' so no
    TPU baseline can ever match its key, AND its failed health verdict
    skips gating entirely."""
    led = tmp_path / "ledger.jsonl"
    _record(led, _bench_doc(fps=50.0, p99=20.0, backend="tpu"))
    fallback = perf_ledger.entry_from_bench(
        _bench_doc(fps=0.3, p99=900.0, backend="cpu", status="failed"))
    assert fallback["baseline_eligible"] is False
    assert fallback["backend_class"] == "cpu"
    entries = perf_ledger.read_ledger(str(led))
    assert perf_ledger.find_baseline(entries, fallback) is None
    perf_ledger.append_entry(str(led), fallback)
    # a failed-health run is reported, never compared — rc 3 ("no
    # gateable number") so a hard-fail gate can't be bypassed by a
    # regression that also breaks health; --warn-only stays green
    assert perf_ledger.main(["--ledger", str(led), "check"]) == 3
    assert perf_ledger.main(
        ["--ledger", str(led), "check", "--warn-only"]) == 0
    # and an HONEST cpu run still never matches the tpu baseline
    honest_cpu = perf_ledger.entry_from_bench(
        _bench_doc(fps=1.0, p99=500.0, backend="cpu"))
    assert perf_ledger.find_baseline(entries, honest_cpu) is None


def test_ledger_degraded_health_never_exits_green(tmp_path):
    """A degraded (not just failed) candidate is equally non-gateable:
    rc 3 without --warn-only, so perf regressions that co-occur with a
    health degradation can't pass a hard-fail gate."""
    led = tmp_path / "ledger.jsonl"
    _record(led, _bench_doc(fps=10.0, p99=80.0))
    _record(led, _bench_doc(fps=6.0, p99=200.0, status="degraded"))
    assert perf_ledger.main(["--ledger", str(led), "check"]) == 3
    assert perf_ledger.main(
        ["--ledger", str(led), "check", "--warn-only"]) == 0


def test_ledger_failed_entry_is_never_a_baseline(tmp_path):
    led = tmp_path / "ledger.jsonl"
    _record(led, _bench_doc(fps=5.0, p99=300.0, backend="cpu",
                            status="failed"))
    cand = perf_ledger.entry_from_bench(_bench_doc(fps=1.0, p99=900.0))
    assert perf_ledger.find_baseline(
        perf_ledger.read_ledger(str(led)), cand) is None


def test_ledger_host_isolation_and_ignore_host(tmp_path):
    led = tmp_path / "ledger.jsonl"
    _record(led, _bench_doc(fps=10.0), host="host-a")
    cand = perf_ledger.entry_from_bench(_bench_doc(fps=5.0),
                                        host="host-b")
    entries = perf_ledger.read_ledger(str(led))
    assert perf_ledger.find_baseline(entries, cand) is None
    assert perf_ledger.find_baseline(entries, cand,
                                     ignore_host=True) is not None


def test_ledger_check_candidate_file_and_report(tmp_path, capsys):
    led = tmp_path / "ledger.jsonl"
    _record(led, _bench_doc(fps=10.0, p99=80.0))
    cand_file = tmp_path / "cand.json"
    cand_file.write_text(json.dumps(_bench_doc(fps=4.0, p99=400.0)))
    assert perf_ledger.main(
        ["--ledger", str(led), "check", "--candidate", str(cand_file),
         "--ignore-host"]) == 1
    assert perf_ledger.main(["--ledger", str(led), "report"]) == 0
    out = capsys.readouterr().out
    assert "encode.dispatch" in out        # top-stage column rendered
    assert "256x128" in out


def test_ledger_check_candidate_not_compared_to_its_own_copy(tmp_path):
    """bench auto-appends every run, so `check --candidate out.json`
    must not match the candidate against its own ledger copy (same rev,
    same numbers) — that would make the gate always pass."""
    led = tmp_path / "ledger.jsonl"
    _record(led, _bench_doc(fps=10.0, p99=80.0))       # the real baseline
    reg_doc = _bench_doc(fps=6.0, p99=200.0)           # a regression run
    _record(led, reg_doc)                              # ...auto-appended
    cand = tmp_path / "out.json"
    cand.write_text(json.dumps(reg_doc))
    assert perf_ledger.main(
        ["--ledger", str(led), "check", "--candidate", str(cand)]) == 1


def test_ledger_check_unknown_health_fails_loudly(tmp_path):
    """Schema drift / wrong file must not silently disable the gate:
    a candidate without a recognisable backend_health errors out."""
    led = tmp_path / "ledger.jsonl"
    _record(led, _bench_doc(fps=10.0, p99=80.0))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"metric": "encode_fps_256x128_h264_tpu",
                               "value": 9.0}))
    assert perf_ledger.main(
        ["--ledger", str(led), "check", "--candidate", str(bad)]) == 2
    assert perf_ledger.main(
        ["--ledger", str(led), "check", "--candidate", str(bad),
         "--warn-only"]) == 0


def test_ledger_chaos_runs_are_ignored_by_check(tmp_path):
    led = tmp_path / "ledger.jsonl"
    _record(led, _bench_doc(fps=1.0, p99=100.0, metric="chaos_recovery"))
    # no encode_fps entry at all -> no candidate; warn-only passes
    assert perf_ledger.main(
        ["--ledger", str(led), "check", "--warn-only"]) == 0


# -------------------------------------------------- cost registry / parser
def test_registry_roofline_and_normalisation():
    reg = perf_mod.PerfRegistry()
    e = reg.record_analysis(
        "step", cost={"flops": 2e9, "bytes accessed": 1.6e9},
        memory={"argument_size_in_bytes": 10, "output_size_in_bytes": 20,
                "temp_size_in_bytes": 30}, backend="tpu",
        device_kind="TPU v5 lite", compile_s=2.0)
    # bandwidth-bound: 1.6e9 B @ 819 GB/s beats 2e9 flop @ 197 TFLOP/s
    assert e["roofline_ms"] == pytest.approx(1.6e9 / 819e9 * 1e3)
    assert e["peak_bytes"] == 60
    rep = reg.report()
    assert rep["count"] == 1
    assert rep["peaks"]["TPU v5 lite"] == {"hbm_gbps": 819.0,
                                           "bf16_tflops": 197.0}
    # compute-bound, and a device with no published peak gets none
    e2 = reg.record_analysis("flop", cost={"flops": 1e12,
                                           "bytes accessed": 1.0},
                             device_kind="TPU v5 lite")
    assert e2["roofline_ms"] == pytest.approx(1e12 / 197e12 * 1e3)
    assert reg.record_analysis("cpu", cost={"flops": 1.0},
                               device_kind="cpu")["roofline_ms"] is None
    json.dumps(rep)                                 # API-serialisable
    # overwrite (recompile after buffer growth) replaces, not duplicates
    reg.record_analysis("step", cost={"flops": 1.0})
    assert reg.report()["count"] == 3      # step, flop, cpu


def test_parse_profile_dir(tmp_path):
    run = tmp_path / "plugins" / "profile" / "r1"
    run.mkdir(parents=True)
    events = [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0, "dur": 4000.0,
         "name": "jit_h264_p_step"},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 0.0, "dur": 2500.0,
         "name": "fusion.42"},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 9999.0,
         "name": "jit_h264_p_step"},     # host copy: must not count
    ]
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    table = perf_mod.parse_profile_dir(
        str(tmp_path), step_names=["h264.p_step[1920x1088]"])
    assert table["device"] is True
    assert table["steps"]["h264.p_step[1920x1088]"]["total_ms"] == \
        pytest.approx(4.0)
    assert table["total_ms"] == pytest.approx(6.5)
    assert table["top_ops"][0]["name"] == "jit_h264_p_step"


def _write_capture(tmp_path, events):
    run = tmp_path / "plugins" / "profile" / "r1"
    run.mkdir(parents=True)
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)


def test_parse_profile_dir_same_stem_steps_do_not_double_count(tmp_path):
    """Two geometries of one program share a stem ('jpeg_step'): the
    capture's events must be claimed once across the table, never
    summed into both rows."""
    _write_capture(tmp_path, [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0, "dur": 4000.0,
         "name": "jit_jpeg_step"},
    ])
    table = perf_mod.parse_profile_dir(
        str(tmp_path), step_names=["jpeg.step[1920x1080@420]",
                                   "jpeg.step[1280x720@420]"])
    total = sum(s["total_ms"] for s in table["steps"].values())
    assert total == pytest.approx(4.0)
    assert len(table["steps"]) == 1
    # and the time is NOT silently credited to whichever geometry sorts
    # first: the row is merged and names both claimants
    row = table["steps"]["jpeg.step[*]"]
    assert row["ambiguous"] == ["jpeg.step[1280x720@420]",
                                "jpeg.step[1920x1080@420]"]


def test_parse_profile_dir_seats_stem_is_distinct_from_single_seat(
        tmp_path):
    """Multi-seat modules compile as jit_h264_seatsN_{mode}_step: their
    device time must land on the seats row, and the single-seat stem
    must not claim it."""
    _write_capture(tmp_path, [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0, "dur": 6000.0,
         "name": "jit_h264_seats2_i_step"},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0, "dur": 1000.0,
         "name": "jit_h264_i_step"},
    ])
    table = perf_mod.parse_profile_dir(
        str(tmp_path), step_names=["h264.i_step[256x128]",
                                   "h264.seats2_i_step[256x128]"])
    assert table["steps"]["h264.seats2_i_step[256x128]"]["total_ms"] == \
        pytest.approx(6.0)
    assert table["steps"]["h264.i_step[256x128]"]["total_ms"] == \
        pytest.approx(1.0)


def test_parse_profile_dir_host_fallback_and_empty(tmp_path):
    assert perf_mod.parse_profile_dir(
        str(tmp_path), step_names=[])["trace_files"] == 0
    run = tmp_path / "p"
    run.mkdir()
    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 1000.0,
         "name": "jit_jpeg_step"},
    ]
    (run / "h.trace.json").write_text(json.dumps({"traceEvents": events}))
    table = perf_mod.parse_profile_dir(
        str(tmp_path), step_names=["jpeg.step[64x64@420]"])
    assert table["device"] is False      # cpu capture: host lane counts
    assert table["steps"]["jpeg.step[64x64@420]"]["total_ms"] == \
        pytest.approx(1.0)


# --------------------------------------------------------- wrap_step (jax)
def test_wrap_step_records_analysis_and_matches_jit():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    reg = perf_mod.PerfRegistry()
    jitted = jax.jit(lambda x: (x.astype(jnp.float32) ** 2).sum())
    wrapped = perf_mod._WrappedStep("test.step", jitted, reg)
    x = jnp.arange(64, dtype=jnp.int32)
    assert float(wrapped(x)) == float(jitted(x))
    rep = reg.report()
    assert rep["count"] == 1
    entry = rep["steps"][0]
    assert entry["name"] == "test.step" and entry["error"] is None
    assert entry["signature"] == "(int32[64])"
    assert entry["compile_s"] is not None
    # second call reuses the AOT executable; no new entries
    assert float(wrapped(x + 1)) == float(jitted(x + 1))
    assert reg.report()["count"] == 1


def test_record_analysis_keeps_zero_compile_s():
    """compile_s=0.0 is a measurement (instant/cached compile), not
    'never measured': it must survive as 0.0, not collapse to null."""
    reg = perf_mod.PerfRegistry()
    e = reg.record_analysis("zero.step", compile_s=0.0)
    assert e["compile_s"] == 0.0
    assert reg.record_analysis("unmeasured.step")["compile_s"] is None


class _FakeJit:
    """A 'jitted' callable whose AOT path is broken: wrap_step must
    fall back to plain dispatch and record the failure. No jax needed —
    numpy arrays carry shape/dtype for the signature."""

    def __init__(self):
        self.calls = 0
        self.lowers = 0

    def __call__(self, x):
        self.calls += 1
        return x + 1

    def lower(self, *args):
        self.lowers += 1
        raise RuntimeError("no AOT for you")


def test_wrap_step_falls_back_when_analysis_breaks():
    import numpy as np
    reg = perf_mod.PerfRegistry()
    fake = _FakeJit()
    wrapped = perf_mod._WrappedStep("broken.step", fake, reg)
    x = np.arange(8)
    # the step still runs (plain dispatch) and the failure is visible
    assert list(wrapped(x)) == list(x + 1)
    entry = reg.report()["steps"][0]
    assert entry["error"] is not None and "no AOT" in entry["error"]
    # the fallback is sticky: no second lowering attempt
    assert list(wrapped(x)) == list(x + 1)
    assert fake.lowers == 1 and fake.calls == 2


def test_note_fallback_counts_and_surfaces_in_report():
    reg = perf_mod.PerfRegistry()
    e = reg.note_fallback("enc.step", "execute_failed", "(uint8[4])")
    assert e["count"] == 1
    reg.note_fallback("enc.step", "execute_failed", "(uint8[4])")
    reg.note_fallback("other.step", "compile_failed")
    rep = reg.report()
    assert [x["step"] for x in rep["fallbacks"]] == \
        ["enc.step", "other.step"]          # most occurrences first
    assert rep["fallbacks"][0]["count"] == 2
    assert rep["fallbacks"][1]["reason"] == "compile_failed"
    reg.clear()
    assert reg.report()["fallbacks"] == []


def test_note_fallback_table_is_bounded():
    reg = perf_mod.PerfRegistry(max_steps=4)
    for i in range(10):
        reg.note_fallback(f"s{i}", "execute_failed")
    assert len(reg.report()["fallbacks"]) == 4


def test_wrap_step_compile_failure_notes_fallback():
    import numpy as np
    reg = perf_mod.PerfRegistry()
    wrapped = perf_mod._WrappedStep("broken.step", _FakeJit(), reg)
    wrapped(np.arange(8))
    fb, = reg.report()["fallbacks"]
    assert fb["step"] == "broken.step"
    assert fb["reason"] == "compile_failed"


def test_wrap_step_execute_failure_notes_fallback_and_incident():
    import numpy as np

    from selkies_tpu.obs.health import engine as health_engine

    class _Compiled:
        def cost_analysis(self):
            return {"flops": 1.0}

        def memory_analysis(self):
            return None

        def __call__(self, x):
            raise RuntimeError("exec boom")

    class _Lowered:
        def cost_analysis(self):
            return {"flops": 1.0}

        def compile(self):
            return _Compiled()

    class _Jit:
        def __call__(self, x):
            return "jit-result"

        def lower(self, *a):
            return _Lowered()

    reg = perf_mod.PerfRegistry()
    wrapped = perf_mod._WrappedStep("exec.step", _Jit(), reg)
    assert wrapped(np.arange(4, dtype=np.int32)) == "jit-result"
    fb, = reg.report()["fallbacks"]
    assert fb["reason"] == "execute_failed"
    assert "int32" in fb["signature"]
    # the permanent fallback is an operator-visible incident
    kinds = [e for e in health_engine.recorder.snapshot()
             if e["kind"] == "wrapped_step_fallback"]
    assert kinds and kinds[-1]["step"] == "exec.step"


def test_kill_switch_fallback_is_not_counted(monkeypatch):
    """SELKIES_PERF_ANALYSIS=0 is a deliberate operator choice, not a
    defect — it must not pollute the fallback incident surface."""
    import numpy as np
    monkeypatch.setenv("SELKIES_PERF_ANALYSIS", "0")
    reg = perf_mod.PerfRegistry()
    fake = _FakeJit()
    wrapped = perf_mod._WrappedStep("ks.step", fake, reg)
    assert list(wrapped(np.arange(8))) == list(np.arange(8) + 1)
    assert reg.report()["fallbacks"] == []
    assert fake.lowers == 0


def test_wrap_step_no_retry_after_donated_input_consumed():
    """A Compiled that dies mid-execution AFTER consuming a donated
    input (reference planes, age counters) must re-raise the real
    device error: retrying plain jit against deleted buffers would mask
    it with 'Array has been deleted'. Fresh inputs still take the
    sticky jit fallback."""
    class _Arg:
        shape = (4,)
        dtype = "int32"
        weak_type = False

        def __init__(self):
            self.deleted = False

        def is_deleted(self):
            return self.deleted

    class _Compiled:
        def cost_analysis(self):
            return {"flops": 1.0}

        def memory_analysis(self):
            return None

        def __call__(self, x):
            x.deleted = True           # donation consumed the buffer
            raise RuntimeError("device boom")

    class _Lowered:
        def cost_analysis(self):
            return {"flops": 1.0}

        def compile(self):
            return _Compiled()

    class _Jit:
        def __init__(self):
            self.calls = 0

        def __call__(self, x):
            self.calls += 1
            return "jit-result"

        def lower(self, *a):
            return _Lowered()

    reg = perf_mod.PerfRegistry()
    jit = _Jit()
    wrapped = perf_mod._WrappedStep("donate.step", jit, reg)
    with pytest.raises(RuntimeError, match="device boom"):
        wrapped(_Arg())
    assert jit.calls == 0              # no masking retry
    # a later call with live inputs uses the sticky jit fallback
    assert wrapped(_Arg()) == "jit-result"
    assert jit.calls == 1


def test_wrap_step_env_kill_switch(monkeypatch):
    import numpy as np
    monkeypatch.setenv("SELKIES_PERF_ANALYSIS", "0")
    reg = perf_mod.PerfRegistry()
    fake = _FakeJit()
    wrapped = perf_mod._WrappedStep("off.step", fake, reg)
    assert list(wrapped(np.arange(4))) == [1, 2, 3, 4]
    assert fake.lowers == 0
    assert reg.report()["count"] == 0


# ------------------------------------------------- profile_h264 increments
def test_profile_writer_incremental_partial_results(tmp_path):
    """The r3 failure mode: a profile killed mid-run must keep every
    completed stage on disk, marked incomplete."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "profile_writer_host", ROOT / "tools" / "profile_h264.py")
    src = (ROOT / "tools" / "profile_h264.py").read_text()
    # lift just the writer class: importing the module pulls in jax and
    # configures the compile cache, which a unit test must not do
    ns: dict = {}
    class_src = src[src.index("class ProfileWriter"):
                    src.index("def t(")]
    exec(compile("import json, os\n" + class_src,  # noqa: S102
                 str(spec.origin), "exec"), ns)
    out = tmp_path / "prof.json"
    w = ns["ProfileWriter"](str(out), meta={"backend": "cpu"})
    w.add("csc", 0.123)
    # simulate the relay dying here: the file already carries stage 1
    doc = json.loads(out.read_text())
    assert doc["complete"] is False
    assert doc["stages"]["csc"]["ms"] == 0.123
    assert doc["backend"] == "cpu"
    w.add("full_i", 88.0, motion_k=9)
    w.finish()
    doc = json.loads(out.read_text())
    assert doc["complete"] is True
    assert set(doc["stages"]) == {"csc", "full_i"}
    assert doc["stages"]["full_i"]["motion_k"] == 9
