"""bench.py driver contract: prints exactly ONE JSON line on stdout with
the keys the driver records (BENCH_r{N}.json). Runs the real bench at a
tiny geometry so the whole thing stays inside the CI budget.

ONE subprocess serves every assertion (a bench run costs ~1 min of jax
import + warm-cache compile; two runs would push tier-1 over its
timeout). The run asks for the CPU explicitly (JAX_PLATFORMS=cpu), so it
is labelled ``cpu`` with an ``ok`` backend verdict; without that
variable a machine with no TPU exits non-zero before measuring anything
(test_bench_refuses_to_run_without_a_tpu). The verdict branches are
unit-tested in tests/test_obs.py::test_backend_verdict_modes."""

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

_cache = {}

#: the run's auto-appended ledger (ISSUE 6) — redirected to a tempdir
#: so the contract run never pollutes the repo's committed trajectory
_LEDGER = os.path.join(tempfile.mkdtemp(prefix="selkies-bench-contract-"),
                       "ledger.jsonl")


def _bench_doc() -> dict:
    if "doc" in _cache:
        return _cache["doc"]
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               BENCH_WIDTH="256", BENCH_HEIGHT="128",
               BENCH_FRAMES="6", BENCH_LAT_BUDGET_S="10",
               BENCH_TP_BUDGET_S="10", BENCH_PIPE_BUDGET_S="15",
               PERF_LEDGER_PATH=_LEDGER)
    r = subprocess.run([sys.executable, str(ROOT / "bench.py")],
                       capture_output=True, text=True, timeout=900,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line: {lines}"
    _cache["doc"] = json.loads(lines[0])
    return _cache["doc"]


def test_bench_emits_single_json_line():
    doc = _bench_doc()
    for key in ("metric", "value", "unit", "vs_baseline", "backend"):
        assert key in doc, key
    assert doc["unit"] == "fps"
    assert isinstance(doc["value"], (int, float))
    # every number names the device it ran on
    assert doc["backend"] in ("cpu", "tpu")
    # per-stage latency attribution (ISSUE 2, re-scoped by ISSUE 10):
    # every stage key present. The ±20% stage-sum-vs-e2e coverage
    # contract is only meaningful FRAME-SERIALLY — and bench.py's stage
    # table + latency_mean_ms come from the ALWAYS-serial IDR loop
    # regardless of BENCH_PIPELINE_DEPTH, so the contract holds (and is
    # asserted) at every depth. The PIPELINED phase is covered by the
    # occupancy identity instead (test_bench_occupancy_block), where
    # stage sum exceeding e2e is the point.
    # The sum is over the frame stages, which never nest: the nested
    # spans (readback.copy, the waits) are parts of them.
    from selkies_tpu.trace import FRAME_STAGES, STAGES
    assert set(doc["stages_ms"]) == set(STAGES)
    stage_sum = doc["stage_sum_ms"]
    e2e = doc["latency_mean_ms"]
    assert stage_sum == round(sum(doc["stages_ms"][s] for s in FRAME_STAGES),
                              3)
    assert abs(stage_sum - e2e) <= 0.2 * e2e, \
        f"stage sum {stage_sum}ms vs e2e {e2e}ms: uninstrumented stall"


def test_bench_device_telemetry_keys():
    """ISSUE 3: HBM peak, compile accounting, and a backend verdict
    accompany every fps line."""
    doc = _bench_doc()
    assert isinstance(doc["hbm_peak_mb"], (int, float))
    assert isinstance(doc["compile_count"], int)
    assert isinstance(doc["compile_total_s"], (int, float))
    assert isinstance(doc["compile_cache_hits"], int)
    assert isinstance(doc["compile_cache_misses"], int)
    assert doc["backend_health"]["status"] in ("ok", "degraded", "failed")


def test_bench_qoe_block():
    """ISSUE 4: a qoe block (ack RTT percentiles, drop rate, composite
    score) rides next to the fps line, computed with the same formula
    /api/sessions documents."""
    from selkies_tpu.obs.qoe import qoe_score
    doc = _bench_doc()
    q = doc["qoe"]
    assert isinstance(q["ack_rtt_p50_ms"], (int, float))
    assert isinstance(q["ack_rtt_p99_ms"], (int, float))
    assert q["ack_rtt_p99_ms"] >= q["ack_rtt_p50_ms"] > 0
    assert q["drop_rate"] == 0.0
    assert 0.0 <= q["score"] <= 100.0
    assert q["score"] == qoe_score(doc["value"], 60.0,
                                   q["ack_rtt_p50_ms"], 0.0)


def test_bench_perf_block():
    """ISSUE 6: static per-step cost analysis (flops, HBM bytes,
    roofline-ms) recorded at compile time rides the JSON line."""
    doc = _bench_doc()
    p = doc["perf"]
    assert p["peaks"]["TPU v5 lite"]["hbm_gbps"] == 819.0
    good = [s for s in p["steps"] if not s.get("error")]
    assert good, f"no analysable steps: {p['steps']}"
    names = {s["name"] for s in good}
    assert any(n.startswith(("h264.", "jpeg.")) for n in names), names
    for s in good:
        assert s["flops"] > 0 and s["bytes_accessed"] > 0
        # the CPU is not in the peak table: no roofline, not a guess
        assert s["roofline_ms"] is None and s["device_kind"] == "cpu"
        assert s["compile_s"] is None or s["compile_s"] >= 0


def test_bench_occupancy_block():
    """ISSUE 6 + 10: the occupancy block now measures the PIPELINE
    phase. The occupancy identity must hold at every depth: per-frame
    critical-path shares + bubble account for the whole frame window
    (stages + bubble == e2e), i.e. the critical path never exceeds the
    stage sum; overlap is the cross-frame window fraction."""
    from selkies_tpu.trace import STAGES
    from selkies_tpu.trace.summary import BUBBLE
    doc = _bench_doc()
    occ = doc["occupancy"]
    assert occ["frames"] > 0
    assert 0.0 <= occ["overlap_fraction"] < 1.0
    shares = occ["critical_path_share"]
    assert set(shares) <= set(STAGES) | {BUBBLE}
    assert abs(sum(shares.values()) + occ["bubble_share"] - 1.0) < 0.05


def test_bench_pipeline_block():
    """ISSUE 10: the deep-pipeline phase documents its configuration —
    depth, pacing period, streaming — so a serial and a depth-2 run at
    the same geometry compare honestly in the ledger."""
    doc = _bench_doc()
    assert doc["pipeline_depth"] == 2          # the default
    p = doc["pipeline"]
    assert p["depth"] == 2 and p["stripe_streaming"] is True
    assert p["period_ms"] > 0 and p["frames"] >= 12
    assert p["sustained_fps"] > 0


def test_bench_ledger_autorecord():
    """ISSUE 6: with PERF_LEDGER_PATH set, the run appends to that
    ledger, labelled with the backend it ran on; an explicit CPU run is
    ``cpu`` with an ``ok`` verdict."""
    _bench_doc()
    sys.path.insert(0, str(ROOT))
    from tools import perf_ledger
    entries = perf_ledger.read_ledger(_LEDGER)
    assert len(entries) == 1, entries
    e = entries[0]
    assert e["backend"] == "cpu"
    assert e["backend_class"] == "cpu"
    assert e["backend_health"] == "ok"
    assert e["baseline_eligible"] is True
    assert e["resolution"] == "256x128"
    # ISSUE 10: the depth/overlap acceptance pair rides every entry
    assert e["pipeline_depth"] == 2
    assert isinstance(e["overlap_fraction"], (int, float))
    # the first healthy run has nothing to compare against yet
    assert perf_ledger.main(["--ledger", _LEDGER, "check"]) == 0


def test_bench_energy_block():
    """ISSUE 14: an energy block (joules/frame, watts over the
    throughput loop, fps/W, honest source label) rides the JSON line,
    with fps_per_w == fps / watts_mean by construction, and the ledger
    entry carries both energy columns non-null."""
    doc = _bench_doc()
    e = doc["energy"]
    assert e["source"] in ("proxy", "rapl", "device")
    assert e["watts_mean"] > 0
    # the idle floor: watts never read zero, whatever the fps
    assert e["watts_mean"] >= e["idle_floor_w"] > 0 \
        or e["source"] != "proxy"
    assert e["joules_frame"] is not None and e["joules_frame"] > 0
    # the pinned identity (fps_per_w is rounded to 4 places)
    assert abs(e["fps_per_w"] - doc["value"] / e["watts_mean"]) < 1e-4
    assert abs(e["joules_frame"] * doc["value"] - e["watts_mean"]) \
        < 0.01 * e["watts_mean"]
    # ledger columns (the pareto subcommand's feed)
    sys.path.insert(0, str(ROOT))
    from tools import perf_ledger
    entry = perf_ledger.read_ledger(_LEDGER)[0]
    assert entry["joules_frame"] == e["joules_frame"]
    assert entry["fps_per_w"] == e["fps_per_w"]
    assert entry["energy_source"] == e["source"]


def test_bench_glass_to_glass_block():
    """ISSUE 7 acceptance: a glass_to_glass block (p50/p99, clock-sync
    quality) rides the JSON line, and g2g >= server-side e2e for EVERY
    frame — min_margin_ms is the per-frame floor of (g2g - e2e), so one
    assertion pins the whole run."""
    doc = _bench_doc()
    g = doc["glass_to_glass"]
    assert g["frames"] > 0
    assert g["p99_ms"] >= g["p50_ms"] > 0
    assert g["mean_ms"] > 0
    # the pin: glass-to-glass can never read better than the server
    # path it contains
    assert g["min_margin_ms"] >= 0.0, g
    # clock-sync quality from the REAL estimator, not a constant
    clock = g["clock"]
    assert clock["synced"] is True
    assert clock["samples"] >= 3 and clock["rejected"] == 0
    assert clock["error_bound_ms"] is not None \
        and clock["error_bound_ms"] < 5.0
    # and the ledger entry carries the g2g trajectory column
    sys.path.insert(0, str(ROOT))
    from tools import perf_ledger
    e = perf_ledger.read_ledger(_LEDGER)[0]
    assert e["g2g_p99_ms"] == g["p99_ms"]
    assert e["g2g_p50_ms"] == g["p50_ms"]


def test_bench_explicit_cpu_run_is_labelled_cpu():
    """An explicit JAX_PLATFORMS=cpu run is labelled ``cpu`` and its
    backend verdict is ``ok`` (nothing fell back to get there)."""
    doc = _bench_doc()
    assert doc["backend"] == "cpu"
    assert doc["backend_health"]["status"] == "ok"
    assert "explicitly requested" in doc["backend_health"]["reason"]


@pytest.mark.parametrize("platform,jax_platforms,want", [
    ("tpu", "", "tpu"),
    ("cpu", "cpu", "cpu"),
    ("cpu", "", None),
    ("cpu", "tpu", None),
])
def test_bench_refuses_to_run_without_a_tpu(monkeypatch, platform,
                                            jax_platforms, want):
    """bench.require_backend with a faked device list: no TPU and no
    explicit JAX_PLATFORMS=cpu exits non-zero at once (no probe budget,
    no fps); nothing here loads libtpu."""
    import types
    sys.path.insert(0, str(ROOT))
    import bench
    monkeypatch.setenv("JAX_PLATFORMS", jax_platforms)
    fake_jax = types.SimpleNamespace(
        devices=lambda: [types.SimpleNamespace(platform=platform)])
    if want is None:
        with pytest.raises(SystemExit) as e:
            bench.require_backend(fake_jax)
        assert e.value.code == 1
    else:
        assert bench.require_backend(fake_jax) == want


def test_bench_prewarm_block():
    """ISSUE 8: the compile-plane view rides the JSON line — the
    ladder-reachable lattice for the bench operating point, with the
    programs this run compiled adopted as warm."""
    doc = _bench_doc()
    p = doc["prewarm"]
    assert p["lattice_size"] >= 2          # base + downscale target
    assert 1 <= p["warmed"] <= p["lattice_size"]
    assert p["deferred_transitions"] == 0  # no ladder runs in main()


def _chaos_doc() -> dict:
    if "chaos" in _cache:
        return _cache["chaos"]
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               BENCH_CHAOS_WIDTH="128", BENCH_CHAOS_HEIGHT="64",
               BENCH_CHAOS_BUDGET_S="90",
               BENCH_CHAOS_COMPILE_DELAY_S="2",
               BENCH_CHAOS_STORM_BUDGET_S="240",
               PERF_LEDGER_PATH=_LEDGER)
    r = subprocess.run([sys.executable, str(ROOT / "bench.py"),
                        "--chaos"],
                       capture_output=True, text=True, timeout=800,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line: {lines}"
    _cache["chaos"] = json.loads(lines[0])
    return _cache["chaos"]


def test_chaos_compile_storm_transitions_stay_compile_free():
    """ISSUE 8 acceptance: with an injected slow compiler
    (encoder.compile:slow), a ladder downscale transition never blocks
    the frame loop on a compile — it defers with a transition_deferred
    incident while the pre-warm worker eats the build in the
    background, then lands with ZERO foreground compiles, and the
    chaos run as a whole still recovers."""
    doc = _chaos_doc()
    assert doc["chaos"]["recovered"] is True
    storm = doc["chaos"]["compile_storm"]
    assert storm["deferred_transitions"] >= 1
    assert storm["landed"] is True and storm["ladder_level"] == 1
    assert storm["foreground_compiles"] == 0
    # the switch itself is session rebuild cost, never a compile: far
    # below the injected compile delay
    assert storm["switch_ms"] < storm["delay_s"] * 1000
    # the background warm demonstrably ate the injected delay
    assert storm["background_compile_s"] >= storm["delay_s"]
    assert storm["prewarm"]["failed"] == 0


def _fleet_doc() -> dict:
    """bench --fleet is pure simulated-host math (no jax): its own
    subprocess costs well under a second, so no caching gymnastics."""
    if "fleet" in _cache:
        return _cache["fleet"]
    env = dict(os.environ, PERF_LEDGER_PATH=_LEDGER)
    r = subprocess.run([sys.executable, str(ROOT / "bench.py"),
                        "--fleet"],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line: {lines}"
    _cache["fleet"] = json.loads(lines[0])
    return _cache["fleet"]


def test_bench_fleet_contract_block():
    """ISSUE 11 acceptance: bench --fleet (3 simulated in-process
    hosts, seeded, injected clock) emits a ``fleet`` block proving the
    serving-architecture contracts: bin-packing stays within per-host
    HBM/pixel budgets, the cold host receives nothing before its
    readiness probe passes, draining a host migrates every seat with
    an IDR resync and zero wedged or dropped sessions, and a killed
    host's seats re-place within the reconnect grace."""
    doc = _fleet_doc()
    assert doc["metric"] == "fleet_contract"
    assert doc["value"] == 1.0
    assert doc["backend_health"]["status"] == "ok"
    f = doc["fleet"]
    assert f["contract_ok"] is True
    assert f["hosts"] == 3
    p = f["placement"]
    assert p["bin_pack_ok"] is True
    assert p["cold_host_placements_before_ready"] == 0
    assert p["placed"] == p["sessions"] and p["pending"] == 0
    d = f["drain"]
    assert d["dropped"] == 0 and d["wedged"] == 0
    assert d["still_on_source"] == 0
    assert d["migrated"] == d["seats"]
    assert d["idr_resyncs"] >= d["migrated"]
    assert d["drained"] is True
    fo = f["failover"]
    assert fo["replaced"] == fo["seats"]
    assert fo["within_grace"] == fo["seats"]
    # every simulated heartbeat crossed the strict wire parser
    assert f["heartbeats"]["rejected"] == 0
    assert f["heartbeats"]["sent"] > 0


@pytest.mark.slow
def test_bench_adaptive_contract_block():
    """ISSUE 15 acceptance shape: bench --adaptive emits an ``adaptive``
    block whose own clauses already gated the exit code (the run exits 1
    on any break), plus the top-level dirty_fraction/content_class
    ledger columns. Slow-marked like the stripe session contract — the
    ``adaptive-bench`` CI job re-proves the full clauses every push;
    this pins the JSON surface the driver and the ledger consume."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               BENCH_ADAPT_WIDTH="128", BENCH_ADAPT_HEIGHT="128",
               BENCH_ADAPT_FRAMES="3", BENCH_ADAPT_REPS="1",
               PERF_LEDGER_PATH=_LEDGER)
    r = subprocess.run([sys.executable, str(ROOT / "bench.py"),
                        "--adaptive"],
                       capture_output=True, text=True, timeout=900,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line: {lines}"
    doc = json.loads(lines[0])
    assert doc["unit"] == "speedup_10pct_vs_full"
    assert "dirty_fraction" in doc and "content_class" in doc
    a = doc["adaptive"]
    assert a["monotonic"] is True
    assert a["byte_identical_full"] is True
    assert a["decode_valid"] is True
    assert a["content_classes_ok"] is True
    points = a["points"]
    assert [p["dirty_fraction"] for p in points] == \
        sorted(p["dirty_fraction"] for p in points)
    for p in points:
        assert p["encode_ms"] > 0 and p["band_rows"] >= 1
    # the ledger row carries the new columns (entry_from_bench)
    rows = [json.loads(ln) for ln in
            Path(_LEDGER).read_text().splitlines()]
    row = [e for e in rows
           if e["metric"].startswith("adaptive_encode_")][-1]
    assert row["dirty_fraction"] == points[0]["dirty_fraction"]
    assert row["adaptive"]["speedup_10pct"] == a["speedup_10pct"]
