#!/usr/bin/env python3
"""The benchmark: ``run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``, one run of one cell of ``BENCHMARK.json`` in one process.

It boots the real server in-process at the cell's configuration, feeds it
the cell's traffic, drives it with one browser-shaped client, warms up
until every program the traffic uses is loaded, measures for
``--seconds``, checks what the client received against the harness's own
source frames, and prints one JSON line. ``--trace 1`` measures the same
window with the program's spans and the profiler on, and prints the
cell's per-layer metrics instead of its end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. The check's controls are run by
``readings.py``, never by a benchmark run.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: the persistent compile cache: a fixed path inside the checkout
CACHE = BENCH / ".cache" / "jax"


def _setup_paths() -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    for p in (str(ROOT), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def execute(bench: dict, cell: dict, config: dict, scene: dict, seed: int,
            seconds: float, trace: bool, devices: list,
            mode: str | None = None, limits: dict | None = None) -> dict:
    """One run; returns the result line as a dict (``check`` last).
    ``mode`` plays a control in the source (``harness.source``): tests
    only."""
    from harness import check, layers, runner, stream
    from harness import trace as tr
    from harness.desktop import Desktop

    flags = config["flags"]
    desktop = Desktop(scene, int(flags["initial_width"]),
                      int(flags["initial_height"]), seed)
    run = runner.Run(cell=cell, config=config, seed=seed, seconds=seconds,
                     desktop=desktop, devices=devices)
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        asyncio.run(runner.serve(run, trace, mode=mode, trace_dir=tdir))
        reduced, rows, offset = None, None, None
        if trace:
            paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise runner.RunError("the profiler wrote no trace")
            rows = tr.events(paths[0])
            offset = tr.clock_offset(rows, run.trace["mark_ns"])
            lo = hi = None
            if offset is not None:
                lo = int(run.trace["lo"] * 1e9) - offset
                hi = int(run.trace["hi"] * 1e9) - offset
            reduced = tr.reduce(rows, lo, hi)

    frames = stream.frames(run.fanout, run.records)
    window = runner.window_frames(run, frames)
    e2e, not_whole, latencies = runner.end_to_end(run, frames, window)
    never = [f for f in window if frames[f].whole_at() is None]
    in_window = sum(1 for t in run.compiles if run.w0 <= t <= run.w1)
    after = sum(1 for t in run.compiles if run.w1 < t <= run.settled_at)
    print(f"[bench] {cell['name']} seed {seed}: setup {run.setup_s:.3f} s "
          f"({run.warmup_notes}); window {seconds} s: {len(window)} frames "
          f"sent, {not_whole} not whole within {runner.GRACE_S} s; "
          f"compiles in the window: {in_window}; after it, until the "
          f"stragglers settled {run.settled_at - run.w1:.2f} s past the "
          f"close: {after}", file=sys.stderr)
    print("[bench] longest latencies (ms, frame, IDR): " + ", ".join(
        f"{ms:.0f} {fid}{' I' if idr else ''}"
        for ms, fid, idr in latencies[:8]), file=sys.stderr)
    for f in never[:3]:
        sent_at = {y: round(t - run.w1, 3) for t, fid, y, _ in run.fanout
                   if fid == f}
        print(f"[bench] frame {f} never arrived whole: sent stripes at "
              f"(y: s past the close) {sent_at}, received "
              f"{sorted(frames[f].got)}", file=sys.stderr)

    t_check = time.monotonic()
    numbers = check.compare(config["codec"], frames, window, run.keys,
                            desktop, seed, check.sampling(config))
    numbers["never_arrived"] = len(never)
    if limits is None:
        limits = runner.limits_for(cell["name"])
    correct, checks = check.verdict(numbers, limits)
    print(f"[bench] check took {time.monotonic() - t_check:.1f} s over "
          f"{numbers['compared']} frames", file=sys.stderr)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out: dict = {"correct": bool(correct), "attempted": len(window),
                 "failed": not_whole + numbers["undecodable"]}
    name = cell["name"]
    if not trace:
        metrics = {}
        for m in bench["end_to_end"]:
            if name in m.get("workloads", [name]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
    else:
        ctx = layers.Context(run=run, frames=frames, trace=reduced,
                             peaks=layers.peaks_for(dev.device_kind))
        metrics = {}
        for m in bench["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            reader = layers.load_module(layers.reader_for(m["name"]))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = reduced["busy_ns"] / 1e9
        device["window_s"] = reduced["window_ns"] / 1e9
        out["device"] = device
        spans = [(s["name"], s["t0_ns"], s["t0_ns"] + s["dur_ns"])
                 for tl in run.spans for s in tl["spans"]]
        # the programs first (stable names across compiles), then the
        # heaviest single ops inside them
        heavy = [[f"program {n}", ns / 1e9]
                 for n, ns in reduced["programs"][:4]]
        heavy += [[n, ns / 1e9] for n, ns in reduced["ops"][:10 - len(heavy)]]
        out["breakdown"] = {
            "device_ops": heavy,
            "idle_gaps": [[tr.label_gap(g, spans, offset), (g[1] - g[0]) / 1e9]
                          for g in reduced["gaps"][:10]]}
    out["check"] = checks
    for k, c in checks.items():
        print(f"[check] {k} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _setup_paths()
    try:
        from harness import runner
        bench, cell, config, scene = runner.load_cell(args.workload)
        devices = runner.require_chip(int(cell["chips"]))
        out = execute(bench, cell, config, scene, args.seed, args.seconds,
                      bool(args.trace), devices)
    except Exception as e:          # a run that cannot report: say why
        traceback.print_exc()
        print(f"run.py: no result: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
