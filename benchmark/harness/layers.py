"""What the per-layer readers share: spans in the window, self time, the
device trace's reduction, and the bytes the window's frames needed.

A reader (``metrics/<name>.py``) defines ``read(ctx) -> float | None``
and returns None when the run gave it nothing to read; the harness then
leaves the metric out of the line.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np

from harness import check

BENCH = Path(__file__).resolve().parent.parent


@dataclasses.dataclass
class Context:
    run: object                 # runner.Run
    frames: dict                # fid -> stream.Frame
    trace: dict | None          # trace.reduce(...) over the traced span
    peaks: dict                 # peaks.json row of this device


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_for(name: str) -> Path:
    """The reader of a per-layer metric: ``metrics/<name>.py``, or for a
    quantity split by the end-to-end metric it moves (``dispatch_ms
    .motion``) the quantity's own ``metrics/dispatch_ms.py``, which every
    split shares."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    return path


def peaks_for(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


# ------------------------------------------------------------------ spans
def _window_timelines(ctx: Context) -> list[dict]:
    lo, hi = ctx.run.w0 * 1e9, ctx.run.w1 * 1e9
    return [tl for tl in ctx.run.spans if lo <= tl["t0_ns"] < hi]


def _self_ns(span: dict, siblings: list[dict]) -> int:
    """A span's duration minus the part its children on the same lane
    cover."""
    s0, s1 = span["t0_ns"], span["t0_ns"] + span["dur_ns"]
    inner = []
    for c in siblings:
        c0, c1 = c["t0_ns"], c["t0_ns"] + c["dur_ns"]
        if c is not span and c["lane"] == span["lane"] and s0 <= c0 \
                and c1 <= s1 and (c0, c1) != (s0, s1):
            inner.append((c0, c1))
    covered, edge = 0, s0
    for c0, c1 in sorted(inner):
        c0 = max(c0, edge)
        if c1 > c0:
            covered += c1 - c0
            edge = c1
    return span["dur_ns"] - covered


def per_frame_self_ms(ctx: Context, names: tuple) -> list[float]:
    """Per frame in the window that recorded any of ``names``: the sum of
    their self times, in ms."""
    out = []
    for tl in _window_timelines(ctx):
        spans = [s for s in tl["spans"] if s["name"] in names]
        if spans:
            out.append(sum(_self_ns(s, tl["spans"]) for s in spans) / 1e6)
    return out


def per_frame_ms(ctx: Context, names: tuple) -> list[float]:
    """Per frame: the summed durations of ``names``, in ms."""
    out = []
    for tl in _window_timelines(ctx):
        d = [s["dur_ns"] for s in tl["spans"] if s["name"] in names]
        if d:
            out.append(sum(d) / 1e6)
    return out


def median(values: list) -> float | None:
    return float(np.median(values)) if values else None


# ----------------------------------------------------------------- device
def idle_pct(ctx: Context) -> float | None:
    t = ctx.trace
    if not t or not t["window_ns"]:
        return None
    return 100.0 * (1.0 - t["busy_ns"] / t["window_ns"])


def traced_frames(ctx: Context) -> list:
    """Frames whose capture began inside the profiler's span."""
    tr = ctx.run.trace
    if not tr:
        return []
    lo, hi = tr["lo"] * 1e9, tr["hi"] * 1e9
    fids = {tl["frame_id"] for tl in ctx.run.spans
            if lo <= tl["t0_ns"] < hi and tl["frame_id"] is not None}
    return [ctx.frames[f] for f in sorted(fids)
            if f in ctx.frames and ctx.frames[f].got]


def dirty_mb_rows(ctx: Context, fid: int, coded_h: int) -> int:
    """Macroblock rows whose pixels the traffic changed between the tick
    before ``fid`` and ``fid`` (the damage an encoder must code)."""
    run, d = ctx.run, ctx.run.desktop
    n_rows = coded_h // 16
    if d.moving:
        return n_rows
    prev = run.keys.get(fid - 1)
    if prev is None:
        return n_rows
    a = d.render(run.keys[fid])
    b = d.render(prev)
    rows = np.any(a != b, axis=(1, 2))
    pad = np.zeros(n_rows * 16, bool)
    pad[:rows.size] = rows
    return int(pad.reshape(n_rows, 16).any(axis=1).sum())


def roofline_pct(ctx: Context) -> float | None:
    """Least time for the bytes the traced frames' work needs, at the
    device's peak bandwidth, over the device's busy time in the traced
    span: bandwidth-bound by construction (see counts/), at the
    configuration's chroma sampling."""
    t = ctx.trace
    frames = traced_frames(ctx)
    if not t or not t["busy_ns"] or not frames:
        return None
    counts = load_module(BENCH / "counts" / f"{ctx.run.config['codec']}.py")
    width, coded_h = ctx.run.config["coded"]
    chroma = check.sampling(ctx.run.config)
    total = 0
    for f in frames:
        total += counts.bytes_needed(
            width, coded_h, intra=f.idr,
            dirty_rows=dirty_mb_rows(ctx, f.fid, coded_h),
            payload_bytes=f.nbytes, chroma=chroma)
    least_s = total / (ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["busy_ns"] / 1e9)
