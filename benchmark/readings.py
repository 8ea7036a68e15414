#!/usr/bin/env python3
"""The readings the check's limits are set from, never a benchmark run:
``readings.py --workload <cell> --seconds <s> --phases <p,...>
[--fidelity-control]``.

One process boots the cell's server once, warms up as a run does, and
then measures one window of ``--seconds`` per phase, at the cell's own
load: a phase is ``<seed>`` (the program as configured, on that seed's
traffic) or ``<mode>:<seed>`` with a mode of the source's
(``harness.source``: ``stale``, ``grey``, and for a configuration that
promises 4:4:4 ``halfchroma``). ``--fidelity-control`` runs
the program at the configuration's ``fidelity_control`` flags instead (a
coarser quality). Once the server is down, each
phase's window is checked as a run's is, and one JSON line per phase
gives every number the check measures, the verdict under the cell's
current limits, and the compiles in the window.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import run as bench_run


def measure(cell: dict, config: dict, scene: dict, seconds: float,
            specs: list, devices: list, limits: dict,
            flags: dict | None = None) -> list[dict]:
    """One process's readings: a dict per phase (``specs``: ``<seed>`` or
    ``<mode>:<seed>``), in order; ``flags`` override the configuration's
    (a fidelity control)."""
    from harness import check, runner, stream
    from harness.desktop import Desktop

    if flags:
        config = {**config, "flags": {**config["flags"], **flags}}
    chroma = check.sampling(config)
    w, h = (int(config["flags"][k]) for k in ("initial_width",
                                              "initial_height"))
    phases = []
    for spec in specs:
        mode, _, seed = str(spec).rpartition(":")
        if mode == "halfchroma" and chroma != "444":
            raise ValueError("halfchroma is a control of a 4:4:4 "
                             f"configuration; {config['name']} promises "
                             f"{chroma}")
        phases.append(runner.Phase(Desktop(scene, w, h, int(seed)),
                                   int(seed), mode or None))
    run = runner.Run(cell=cell, config=config, seed=phases[0].seed,
                     seconds=seconds, desktop=phases[0].desktop,
                     devices=devices)
    asyncio.run(runner.serve(run, False, phases=tuple(phases)))
    print(f"[readings] {cell['name']}: setup {run.setup_s:.3f} s "
          f"({run.warmup_notes}); peak {run.memory_peak_bytes} B",
          file=sys.stderr)

    frames = stream.frames(run.fanout, run.records)
    out = []
    for k, ph in enumerate(phases, 1):
        t = time.monotonic()
        window = [f for f in runner.window_frames(run, frames, ph.w0, ph.w1)
                  if run.phase.get(f) == k]
        numbers = check.compare(config["codec"], frames, window, run.keys,
                                ph.desktop, ph.seed, chroma)
        numbers["never_arrived"] = sum(
            1 for f in window if frames[f].whole_at() is None)
        correct, _ = check.verdict(numbers, limits)
        out.append({
            "cell": cell["name"], "seed": ph.seed, "mode": ph.mode,
            "flags": flags or None,
            "frames": len(window), "numbers": numbers, "correct": correct,
            "compiles_in_window": sum(1 for c in run.compiles
                                      if ph.w0 <= c <= ph.w1),
            "check_s": round(time.monotonic() - t, 2)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--phases", required=True)
    ap.add_argument("--fidelity-control", action="store_true")
    args = ap.parse_args(argv)
    bench_run._setup_paths()
    from harness import runner

    _, cell, config, scene = runner.load_cell(args.workload)
    devices = runner.require_chip(int(cell["chips"]))
    flags = config["fidelity_control"]["flags"] \
        if args.fidelity_control else None
    for line in measure(cell, config, scene, args.seconds,
                        args.phases.split(","), devices,
                        runner.limits_for(cell["name"]), flags):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
