"""What the readers of the program's wait spans, CPU times and counters
share beyond ``layers`` (``metrics/copy_ms.py`` and the others of
PERF.md §3).

The program records a wait on the lane ``<lane>.wait`` of the lane it
blocks in, a work span's thread CPU time as ``cpu_ns``, and per-frame
counters under ``counts`` (``selkies_tpu/trace``). A program without
them gives these readers nothing: each then returns None and the
harness leaves the metric out of the line.
"""

from __future__ import annotations

from harness import layers

WAIT_SUFFIX = ".wait"


def end(span: dict) -> int:
    return span["t0_ns"] + span["dur_ns"]


def inside(inner: dict, outer: dict) -> bool:
    return outer["t0_ns"] <= inner["t0_ns"] and end(inner) <= end(outer)


def nested_wait_ns(span: dict, spans: list[dict]) -> int:
    """The time of the waits on ``span``'s wait lane that lie within it
    (the waits of one lane never overlap: a thread blocks once at a
    time)."""
    lane = span["lane"] + WAIT_SUFFIX
    return sum(w["dur_ns"] for w in spans
               if w["lane"] == lane and inside(w, span))


def per_frame(ctx, fn) -> list[float]:
    """``fn(spans) -> ns | None`` for each frame in the window, in ms;
    frames for which it gives None are left out."""
    out = []
    for tl in layers._window_timelines(ctx):
        ns = fn(tl["spans"])
        if ns is not None:
            out.append(ns / 1e6)
    return out


def counts(ctx, name: str) -> list[int]:
    """The counter ``name`` of each frame in the window that counted it."""
    return [tl["counts"][name] for tl in layers._window_timelines(ctx)
            if name in tl.get("counts", {})]
