"""Chrome trace-event JSON export (the Perfetto / chrome://tracing format).

Emits the JSON Object Format: ``{"traceEvents": [...], "displayTimeUnit":
"ms"}`` with complete (``ph: "X"``) events for spans, instant (``ph:
"i"``) events for zero-duration markers, and ``M`` metadata events naming
the process and one thread track per lane (a wait lane ``<lane>.wait`` is
a track of its own). Timestamps are microseconds of ``CLOCK_MONOTONIC``,
the tracer's clock. A work span's ``cpu_ns`` rides its ``args``; the
frame's counters ride the frame envelope's ``args.counts``.
"""

from __future__ import annotations

from typing import Iterable, Union

from .core import FrameTimeline

PID = 1
#: tid reserved for the per-frame envelope track
FRAME_TID = 0


def _as_dict(tl: Union[FrameTimeline, dict]) -> dict:
    return tl if isinstance(tl, dict) else tl.to_dict()


def to_trace_events(timelines: Iterable[Union[FrameTimeline, dict]],
                    process_name: str = "selkies-tpu") -> dict:
    """Render timelines to a Chrome trace-event document (plain dict,
    ``json.dumps``-ready)."""
    events: list[dict] = [{
        "ph": "M", "pid": PID, "tid": FRAME_TID, "name": "process_name",
        "args": {"name": process_name},
    }, {
        "ph": "M", "pid": PID, "tid": FRAME_TID, "name": "thread_name",
        "args": {"name": "frames"},
    }]
    lanes: dict[str, int] = {}

    def tid_for(lane: str) -> int:
        tid = lanes.get(lane)
        if tid is None:
            tid = lanes[lane] = len(lanes) + 1
            events.append({"ph": "M", "pid": PID, "tid": tid,
                           "name": "thread_name", "args": {"name": lane}})
        return tid

    for tl in timelines:
        d = _as_dict(tl)
        fid = d.get("frame_id")
        frame_args = {"frame_id": fid, "display": d.get("display_id")}
        if d.get("t1_ns") is not None:
            env_args = frame_args
            if d.get("counts"):
                env_args = dict(frame_args, counts=dict(d["counts"]))
            events.append({
                "name": f"frame {fid}", "ph": "X", "pid": PID,
                "tid": FRAME_TID, "ts": d["t0_ns"] / 1e3,
                "dur": (d["t1_ns"] - d["t0_ns"]) / 1e3, "args": env_args,
            })
        for s in d.get("spans", []):
            tid = tid_for(s["lane"])
            if s["dur_ns"] <= 0:
                events.append({
                    "name": s["name"], "ph": "i", "s": "t", "pid": PID,
                    "tid": tid, "ts": s["t0_ns"] / 1e3, "args": frame_args,
                })
            else:
                args = frame_args
                if s.get("cpu_ns") is not None:
                    args = dict(frame_args, cpu_ns=s["cpu_ns"])
                events.append({
                    "name": s["name"], "ph": "X", "pid": PID, "tid": tid,
                    "ts": s["t0_ns"] / 1e3, "dur": s["dur_ns"] / 1e3,
                    "args": args,
                })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def timelines_from_events(events: Iterable[dict]) -> list[dict]:
    """Inverse of :func:`to_trace_events` for COMPLETED frames: rebuild
    timeline dicts (t0_ns/t1_ns/spans) from an exported document so the
    occupancy analyzer runs identically on a saved /api/trace snapshot.
    Spans re-attach by ``args.frame_id``+``args.display``; lanes come
    from the thread_name metadata. Frames whose envelope event was never
    exported (still open at export time) are dropped — interval math
    needs a closed window."""
    thread_names: dict[tuple, str] = {}
    frames: dict[tuple, dict] = {}
    spans: list[tuple[tuple, dict, object]] = []
    for e in events:
        if not isinstance(e, dict):
            continue
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            thread_names[(e.get("pid"), e.get("tid"))] = str(
                (e.get("args") or {}).get("name", ""))
            continue
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        fid = args.get("frame_id")
        if fid is None:
            continue
        key = (args.get("display"), fid)
        name = str(e.get("name", "?"))
        if name.startswith("frame "):
            frames[key] = {
                "display_id": args.get("display"), "frame_id": fid,
                "t0_ns": int(float(e["ts"]) * 1e3),
                "t1_ns": int((float(e["ts"]) + float(e["dur"])) * 1e3),
                "spans": [],
                "counts": dict(args.get("counts") or {}),
            }
        else:
            spans.append((key, e, (e.get("pid"), e.get("tid"))))
    for key, e, tkey in spans:
        tl = frames.get(key)
        if tl is None:
            continue
        span = {
            "name": str(e.get("name", "?")),
            "lane": thread_names.get(tkey) or str(tkey[1]),
            "t0_ns": int(float(e["ts"]) * 1e3),
            "dur_ns": int(float(e.get("dur", 0)) * 1e3),
        }
        cpu = (e.get("args") or {}).get("cpu_ns")
        if cpu is not None:
            span["cpu_ns"] = int(cpu)
        tl["spans"].append(span)
    return [frames[k] for k in sorted(frames, key=lambda k: frames[k]["t0_ns"])]


def events_from_document(doc) -> list[dict]:
    """Accept either the object form ({"traceEvents": [...]}) or the bare
    JSON-array form — both are valid on the import side of the viewers."""
    if isinstance(doc, dict):
        events = doc.get("traceEvents", [])
    elif isinstance(doc, list):
        events = doc
    else:
        raise ValueError("not a trace-event document")
    if not isinstance(events, list):
        raise ValueError("traceEvents must be a list")
    return [e for e in events if isinstance(e, dict)]
