"""Small-size cells for the CPU tests: the real configurations and scenes,
cut to a geometry the CPU can run in seconds. Nothing here is measured."""

from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
WIDTH, HEIGHT = 256, 128


def config(name: str, chroma: str = "420") -> dict:
    """A configuration at the tiny geometry; at ``chroma="444"`` its
    4:4:4 variant: the program's ``fullcolor`` flag on, and the sampling
    the check holds the stream to."""
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    c["flags"].update(initial_width=WIDTH, initial_height=HEIGHT)
    if chroma == "444":
        c["flags"]["fullcolor"] = True
        c["chroma"] = "444"
    c["coded"] = [WIDTH, -(-HEIGHT // 64) * 64]
    c["warmup"] = {"min_s": 2, "min_frames": 5, "quiet_s": 2, "max_s": 600}
    c["trace_s"] = 1
    return c


def scene(mix: str) -> dict:
    s = copy.deepcopy(json.loads((BENCH / "traffic" / f"{mix}.json")
                                 .read_text()))
    bg = s["background"]
    if bg["kind"] == "texture":
        bg["size"] = [WIDTH * 2, HEIGHT * 2]
        bg["octaves_px"] = [64, 16, 8, 4]
        bg["blobs"] = [[24, 0.2, 0.22], [12, 0.25, 0.2]]
    else:
        bg["rects"] = [[0, 0, WIDTH, 8, [48, 52, 60]]]
    if s.get("sprites"):
        s["sprites"].update(count=2, size_px=[16, 32])
    if s.get("text_pane"):
        s["text_pane"].update(x=16, y=24, cols=24, rows=5)
    return s


def cell(name: str, config_name: str, mix: str) -> dict:
    return {"name": name, "config": config_name, "traffic": mix, "chips": 1}
