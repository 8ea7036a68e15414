"""Per-stage latency summarizer + pipeline occupancy analyzer.

Two instruments over the same frame timelines:

- **per-stage percentiles** (:func:`summarize_timelines`): p50/p99/mean
  per span name — the BENCH_r*.json breakdown and the
  ``selkies_stage_ms`` histogram feed;
- **occupancy / critical path** (:func:`occupancy_report`): which stage
  actually *bounded* each frame's end-to-end time. Stage-sum coverage
  (the PR-2 20% contract) stops being meaningful the moment stages
  overlap — a deep pipeline's stage sum exceeds e2e by design — so the
  acceptance instrument for the pipeline rework is interval math:
  per-frame critical-path attribution (each instant of the frame window
  is charged to the covering span that ends last — the stage still
  gating completion — or to ``bubble`` when nothing runs), an overlap
  fraction (0 for a fully-serial pipeline), and per-lane occupancy /
  largest-gap detection over the whole timeline window. Wait spans (on
  ``<lane>.wait``) are no work: these views leave them out, so a frame's
  time spent only waiting reads as bubble.

Consumes either live :class:`~.core.FrameTimeline`s or the exported
Chrome trace-event JSON (the offline CLI path), so a BENCH_r*.json
breakdown and a saved /api/trace snapshot summarize identically.
"""

from __future__ import annotations

from typing import Iterable, Optional, Union

from .core import WAIT_SUFFIX, FrameTimeline


def _pct(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile on an ascending list (the same convention
    bench.py uses for its p50/p99 line)."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(len(sorted_vals) * q))
    return sorted_vals[i]


def summarize_durations(by_stage: dict[str, list[float]]) -> dict[str, dict]:
    """{stage: [ms, ...]} -> {stage: {count, p50_ms, p99_ms, mean_ms,
    total_ms}}, stages sorted by total time descending."""
    out: dict[str, dict] = {}
    for name, vals in by_stage.items():
        vals = sorted(vals)
        total = sum(vals)
        out[name] = {
            "count": len(vals),
            "p50_ms": round(_pct(vals, 0.50), 3),
            "p99_ms": round(_pct(vals, 0.99), 3),
            "mean_ms": round(total / len(vals), 3) if vals else 0.0,
            "total_ms": round(total, 3),
        }
    return dict(sorted(out.items(),
                       key=lambda kv: -kv[1]["total_ms"]))


def _is_wait(span: dict) -> bool:
    return (span.get("lane") or "").endswith(WAIT_SUFFIX)


def summarize_timelines(timelines: Iterable[Union[FrameTimeline, dict]],
                        waits: bool = True) -> dict[str, dict]:
    """Per-stage percentiles; ``waits=False`` leaves out the spans on
    wait lanes (``<lane>.wait``)."""
    by_stage: dict[str, list[float]] = {}
    for tl in timelines:
        d = tl if isinstance(tl, dict) else tl.to_dict()
        for s in d.get("spans", []):
            if not waits and _is_wait(s):
                continue
            if s["dur_ns"] > 0:
                by_stage.setdefault(s["name"], []).append(s["dur_ns"] / 1e6)
    return summarize_durations(by_stage)


def summarize_events(events: Iterable[dict]) -> dict[str, dict]:
    """Summarize exported trace events: complete (``X``) spans only; the
    per-frame envelope track ('frame N' names) is excluded so stage sums
    aren't double-counted."""
    by_stage: dict[str, list[float]] = {}
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        name = str(e.get("name", "?"))
        if name.startswith("frame "):
            continue
        by_stage.setdefault(name, []).append(float(e["dur"]) / 1e3)
    return summarize_durations(by_stage)


def frame_latency_ms(timelines: Iterable[Union[FrameTimeline, dict]]
                     ) -> list[float]:
    """Completed frames' begin->end wall times (the e2e the stage sum is
    validated against)."""
    out = []
    for tl in timelines:
        d = tl if isinstance(tl, dict) else tl.to_dict()
        if d.get("t1_ns") is not None:
            out.append((d["t1_ns"] - d["t0_ns"]) / 1e6)
    return out


#: pseudo-stage charged with frame-window time no span covers (host
#: gaps, scheduling stalls, untraced work)
BUBBLE = "bubble"


def frame_critical_path(tl: Union[FrameTimeline, dict]) -> Optional[dict]:
    """Interval attribution for ONE completed frame.

    Every instant of ``[t0, t1]`` is charged to exactly one account:
    the covering span that ends last (several stages running at once —
    the one finishing last is the one gating completion), or
    :data:`BUBBLE` when no work span covers it (waits are left out). By
    construction
    ``sum(stages) + bubble == e2e`` exactly; for a fully-serial pipeline
    each stage's charge equals its duration, so the critical path
    equals the stage sum.

    ``overlap_fraction`` = 1 - union/stage-sum: 0.0 when no two spans
    ever overlap, approaching 1.0 as everything runs concurrently.
    Returns None for open frames or frames with no positive spans.
    """
    d = tl if isinstance(tl, dict) else tl.to_dict()
    if d.get("t1_ns") is None:
        return None
    t0f, t1f = d["t0_ns"], d["t1_ns"]
    ivs: list[tuple[int, int, str]] = []
    for s in d.get("spans", []):
        if s["dur_ns"] <= 0 or _is_wait(s):
            continue
        a = max(s["t0_ns"], t0f)
        b = min(s["t0_ns"] + s["dur_ns"], t1f)
        if b > a:
            ivs.append((a, b, s["name"]))
    if not ivs:
        return None
    points = sorted({t0f, t1f, *(a for a, _, _ in ivs),
                     *(b for _, b, _ in ivs)})
    stages: dict[str, float] = {}
    bubble_ns = 0
    for p, q in zip(points, points[1:]):
        cover = [iv for iv in ivs if iv[0] <= p and iv[1] >= q]
        if not cover:
            bubble_ns += q - p
            continue
        # the gating span: latest end, then latest start for stability
        _, _, name = max(cover, key=lambda iv: (iv[1], iv[0], iv[2]))
        stages[name] = stages.get(name, 0.0) + (q - p)
    e2e_ns = t1f - t0f
    sum_ns = sum(b - a for a, b, _ in ivs)
    union_ns = e2e_ns - bubble_ns
    return {
        "e2e_ms": e2e_ns / 1e6,
        "bubble_ms": bubble_ns / 1e6,
        "stage_sum_ms": sum_ns / 1e6,
        "overlap_fraction": max(0.0, 1.0 - union_ns / sum_ns)
        if sum_ns > 0 else 0.0,
        "stages": {n: v / 1e6 for n, v in stages.items()},
    }


def frame_accounts(timelines: Iterable[Union[FrameTimeline, dict]]
                   ) -> list[dict]:
    """Per-frame critical-path accounts with their frame/session
    identity attached: one dict per COMPLETED frame carrying
    ``display_id`` / ``frame_id`` / the wall window plus the
    :func:`frame_critical_path` attribution (``stages + bubble == e2e``
    exactly). This is the join surface the energy plane charges watts
    against (obs/energy.attribute_timelines): any account that sums to
    the frame window in milliseconds sums to the frame's joules at a
    fixed power draw."""
    out: list[dict] = []
    for tl in timelines:
        d = tl if isinstance(tl, dict) else tl.to_dict()
        cp = frame_critical_path(d)
        if cp is None:
            continue
        out.append({
            "display_id": d.get("display_id"),
            "frame_id": d.get("frame_id"),
            "t0_ns": d["t0_ns"],
            "t1_ns": d["t1_ns"],
            "e2e_ms": cp["e2e_ms"],
            "bubble_ms": cp["bubble_ms"],
            "stages": cp["stages"],
        })
    return out


def _merge_intervals(ivs: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(ivs):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def lane_occupancy(timelines: Iterable[Union[FrameTimeline, dict]]
                   ) -> dict[str, dict]:
    """Per-lane busy fraction over the whole observed window — the
    deep-pipeline acceptance view: after the rework every lane should
    stay busy (occupancy -> 1 for the bottleneck lane) instead of the
    frame-serial pattern where every lane idles while one works.
    ``largest_gap_ms`` is the worst bubble inside the window. Wait lanes
    are not reported: a thread blocked is no lane busy."""
    by_lane: dict[str, list[tuple[int, int]]] = {}
    w0: Optional[int] = None
    w1: Optional[int] = None
    for tl in timelines:
        d = tl if isinstance(tl, dict) else tl.to_dict()
        if d.get("t1_ns") is None:
            continue
        w0 = d["t0_ns"] if w0 is None else min(w0, d["t0_ns"])
        w1 = d["t1_ns"] if w1 is None else max(w1, d["t1_ns"])
        for s in d.get("spans", []):
            if s["dur_ns"] > 0 and not _is_wait(s):
                by_lane.setdefault(s.get("lane") or "?", []).append(
                    (s["t0_ns"], s["t0_ns"] + s["dur_ns"]))
    if w0 is None or w1 is None or w1 <= w0:
        return {}
    window_ns = w1 - w0
    out: dict[str, dict] = {}
    for lane, ivs in by_lane.items():
        # clip to the frame-envelope window (a ws.send span adopted by
        # frame-id can outlive its frame's t1): busy must never exceed
        # the denominator, or occupancy reads > 100%
        clipped = [(max(a, w0), min(b, w1)) for a, b in ivs
                   if min(b, w1) > max(a, w0)]
        merged = _merge_intervals(clipped)
        busy = sum(b - a for a, b in merged)
        gaps = []
        prev = w0
        for a, b in merged:
            gaps.append(a - prev)
            prev = max(prev, b)
        gaps.append(w1 - prev)
        out[lane] = {
            "busy_ms": round(busy / 1e6, 3),
            "window_ms": round(window_ns / 1e6, 3),
            "occupancy": round(busy / window_ns, 4),
            "largest_gap_ms": round(max(0, *gaps) / 1e6, 3),
        }
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["occupancy"]))


def window_overlap_fraction(dicts: list) -> float:
    """Cross-frame span overlap over the whole observed window:
    ``1 - union(all spans)/sum(all spans)`` across every completed
    frame's spans together. A frame-serial engine reads ~0 (consecutive
    frames' spans never coexist); a depth-N pipeline reads the fraction
    of span time that genuinely ran concurrently — frame N+1's
    ``encode.dispatch`` under frame N's readback/packetize. This is THE
    deep-pipeline acceptance number (ROADMAP 2): per-frame stages of a
    pipelined engine still run in sequence *within* each frame, so only
    the window view can see the overlap. A span nested in another of
    its frame on the same lane (``readback.copy`` in ``encode.readback``)
    is no overlap, and waits (``<lane>.wait``) are no work: each frame's
    lane counts as the union of its spans, wait lanes not at all."""
    ivs: list[tuple[int, int]] = []
    total = 0
    for d in dicts:
        if d.get("t1_ns") is None:
            continue
        by_lane: dict[str, list[tuple[int, int]]] = {}
        for s in d.get("spans", []):
            if s["dur_ns"] > 0 and not _is_wait(s):
                by_lane.setdefault(s.get("lane") or "?", []).append(
                    (s["t0_ns"], s["t0_ns"] + s["dur_ns"]))
        for lane_ivs in by_lane.values():
            merged = _merge_intervals(lane_ivs)
            ivs.extend(merged)
            total += sum(b - a for a, b in merged)
    if total <= 0:
        return 0.0
    union = sum(b - a for a, b in _merge_intervals(ivs))
    return max(0.0, 1.0 - union / total)


def occupancy_report(timelines: Iterable[Union[FrameTimeline, dict]]
                     ) -> dict:
    """Aggregate occupancy / critical-path analysis over completed
    frames. ``overlap_fraction`` is the WINDOW-level cross-frame overlap
    (:func:`window_overlap_fraction`); the per-frame identity
    ``stages + bubble == e2e`` still holds exactly per frame, and the
    per-stage ``critical_path`` shares come from the per-frame totals
    (not a mean of ratios), so long frames weigh what they should."""
    dicts = [tl if isinstance(tl, dict) else tl.to_dict()
             for tl in timelines]
    per = [cp for cp in (frame_critical_path(d) for d in dicts)
           if cp is not None]
    if not per:
        return {"frames": 0, "overlap_fraction": 0.0, "bubble_share": 0.0,
                "critical_path": {}, "e2e_ms": {}, "lanes": {}}
    e2e = sorted(cp["e2e_ms"] for cp in per)
    e2e_total = sum(e2e)
    bubble_total = sum(cp["bubble_ms"] for cp in per)
    stage_tot: dict[str, float] = {}
    for cp in per:
        for name, ms in cp["stages"].items():
            stage_tot[name] = stage_tot.get(name, 0.0) + ms
    critical = {
        name: {"ms": round(tot / len(per), 3),
               "share": round(tot / e2e_total, 4) if e2e_total else 0.0}
        for name, tot in sorted(stage_tot.items(), key=lambda kv: -kv[1])}
    return {
        "frames": len(per),
        "overlap_fraction": round(window_overlap_fraction(dicts), 4),
        "bubble_share": round(bubble_total / e2e_total, 4)
        if e2e_total else 0.0,
        "critical_path": critical,
        "e2e_ms": {"mean": round(e2e_total / len(e2e), 3),
                   "p50": round(_pct(e2e, 0.50), 3),
                   "p99": round(_pct(e2e, 0.99), 3)},
        "lanes": lane_occupancy(dicts),
    }


def render_occupancy(report: dict) -> str:
    """Human table for the CLI / bench stderr."""
    lines = [f"frames={report['frames']} "
             f"overlap={report['overlap_fraction']:.1%} "
             f"bubble={report['bubble_share']:.1%} "
             f"e2e_p50={report['e2e_ms'].get('p50', 0.0)}ms"]
    lines.append(f"{'critical path':<18} {'mean_ms':>9} {'share':>7}")
    for name, s in report["critical_path"].items():
        lines.append(f"{name:<18} {s['ms']:>9.3f} {s['share']:>6.1%}")
    if report["lanes"]:
        lines.append(f"{'lane':<18} {'busy_ms':>9} {'occup':>7} "
                     f"{'max_gap_ms':>11}")
        for lane, s in report["lanes"].items():
            lines.append(f"{lane:<18} {s['busy_ms']:>9.3f} "
                         f"{s['occupancy']:>6.1%} "
                         f"{s['largest_gap_ms']:>11.3f}")
    return "\n".join(lines)


def render_table(summary: dict[str, dict]) -> str:
    """Fixed-width human table for the CLI / bench stderr."""
    lines = [f"{'stage':<18} {'count':>6} {'p50_ms':>9} {'p99_ms':>9} "
             f"{'mean_ms':>9} {'total_ms':>10}"]
    for name, s in summary.items():
        lines.append(f"{name:<18} {s['count']:>6} {s['p50_ms']:>9.3f} "
                     f"{s['p99_ms']:>9.3f} {s['mean_ms']:>9.3f} "
                     f"{s['total_ms']:>10.3f}")
    return "\n".join(lines)
