"""The check decides ``correct`` on a whole run: the server, the client,
the window and the comparison, on the CPU at small size, with the chip's
look skipped. The sound run passes; the controls and each fault planted
in the timed path fail.

The controls (``readings.py``): every frame one refresh stale (the
freshness guarantee), every frame grey (colour, the fidelity guarantee),
under a 4:4:4 promise every frame's colour averaged to 4:2:0
(``halfchroma``, the sampling guarantee), and the program at the
configuration's coarser ``fidelity_control`` quality (quantisation, the
fidelity guarantee). A 4:2:0 stream under a 4:4:4 promise does not
decode. Each cell runs at 4:2:0, as configured, and the motion cells
also as their 4:4:4 variant (``tiny.config(name, "444")``). The faults
a cell on one chip can have: a step that returns its state unchanged
(H.264's reference planes, all three or the chroma alone, and the chroma
alone at 4:4:4), half of the batch left out (half of JPEG's stripes), and
an answer altered where it is produced (a byte of a frame's payload).
There is no exchange between chips to leave out.

At this size (CPU, PR 22) sound runs read worst luma block 35-37 (H.264)
and 48-80 (JPEG), worst chroma block 44-47 and 186-221, worst frame's
luma 21.7-22.0 and 13.3-14.5; the stale control 4676 and 6645 (luma
block), grey 5414 (chroma block), the fidelity control's worst frame's
luma 107-114 (CRF 37) and 41-44 (quality 12 in the scan; the
configuration's 10 is coarser). The 4:4:4 variants (CPU, PR 25) read
worst chroma block (16x16) 34.1 (H.264) and 176.6-295.1 (JPEG) sound,
419.3 and 346.0-693.2 under halfchroma, 4826 under grey: H.264's own
limit on it sits between (``H264_444_CHROMA``); JPEG's halfchroma reads
only 1.2-3.9x its sound runs, so no limit separates them and the test
does not run it. The test's limits sit between them; the chip's are in
``limits/``, set from chip runs.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from tests import tiny

BENCH = Path(__file__).resolve().parent.parent
LIMITS = {"worst_luma_mse": 600.0, "worst_chroma_mse": 1000.0,
          "frame_luma_mse": 33.0, "undecodable": 0, "never_arrived": 0}
#: the 4:4:4 H.264 variant's limit on its worst 16x16 chroma block,
#: between its sound reading and halfchroma's
H264_444_CHROMA = 150.0
SEED = 2**35 + 99
CELLS = [("h264-1080p60", "motion", "420"), ("h264-1080p60", "typing", "420"),
         ("jpeg-1080p60", "motion", "420"), ("h264-1080p60", "motion", "444"),
         ("jpeg-1080p60", "motion", "444")]
CELL_IDS = [f"{c}-{m}" + ("-444" if ch == "444" else "")
            for c, m, ch in CELLS]


def limits(config_name, chroma):
    if (config_name, chroma) == ("h264-1080p60", "444"):
        return {**LIMITS, "worst_chroma_mse": H264_444_CHROMA}
    return LIMITS


def run_cell(config_name, mix, chroma="420", config=None):
    import run
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return run.execute(bench, tiny.cell(f"{config_name}.{mix}", config_name,
                                        mix),
                       config or tiny.config(config_name, chroma),
                       tiny.scene(mix), SEED, 3.0, False, jax.devices(),
                       limits=limits(config_name, chroma))


def readings(config_name, mix, specs, flags=None, chroma="420"):
    import readings as r
    return r.measure(tiny.cell(f"{config_name}.{mix}", config_name, mix),
                     tiny.config(config_name, chroma), tiny.scene(mix), 3.0,
                     specs, jax.devices(), limits(config_name, chroma), flags)


@pytest.mark.parametrize("config_name,mix,chroma", CELLS, ids=CELL_IDS)
def test_sound_run_is_correct(config_name, mix, chroma):
    sound = run_cell(config_name, mix, chroma)
    assert sound["correct"], sound["check"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert list(sound)[-1] == "check"
    assert set(sound["check"]) == set(LIMITS)


@pytest.mark.parametrize("config_name,mix,chroma", CELLS, ids=CELL_IDS)
def test_stale_and_grey_controls_fail(config_name, mix, chroma):
    """Grey only under motion: the typing desktop is itself near grey.
    Under a 4:4:4 H.264 promise, colour averaged to 4:2:0 fails too; JPEG
    at quality 60 quantises away most of what the averaging removes, so
    there halfchroma does not separate (module docstring)."""
    specs = [SEED, f"stale:{SEED + 1}"]
    if mix == "motion":
        specs.append(f"grey:{SEED + 2}")
    if chroma == "444" and config_name.startswith("h264"):
        specs.append(f"halfchroma:{SEED + 3}")
    sound, stale, *colour = readings(config_name, mix, specs, chroma=chroma)
    assert sound["correct"], sound["numbers"]
    assert sound["compiles_in_window"] == 0
    n = sound["numbers"]
    assert not stale["correct"]
    assert stale["numbers"]["worst_luma_mse"] > 3 * n["worst_luma_mse"]
    for g in colour:
        assert not g["correct"], (g["mode"], g["numbers"])
        # grey and halfchroma keep the luma: only the chroma comparison
        # catches them
        assert g["numbers"]["worst_luma_mse"] <= LIMITS["worst_luma_mse"]
        assert g["numbers"]["worst_chroma_mse"] > 3 * n["worst_chroma_mse"]


def test_halfchroma_is_refused_under_a_420_promise():
    with pytest.raises(ValueError, match="halfchroma"):
        readings("h264-1080p60", "motion", [f"halfchroma:{SEED}"])


@pytest.mark.parametrize("config_name", ["h264-1080p60", "jpeg-1080p60"])
def test_420_stream_under_a_444_promise_is_undecodable(config_name):
    """The sampling is the configuration's promise, not the stream's:
    the program coding 4:2:0 where 4:4:4 is promised does not decode."""
    config = tiny.config(config_name, "444")
    config["flags"]["fullcolor"] = False
    out = run_cell(config_name, "motion", config=config)
    assert not out["correct"], out["check"]
    assert out["check"]["undecodable"]["value"] > 0


@pytest.mark.parametrize("config_name", ["h264-1080p60", "jpeg-1080p60"])
def test_fidelity_control_fails(config_name):
    flags = tiny.config(config_name)["fidelity_control"]["flags"]
    (coarse,) = readings(config_name, "motion", [SEED], flags)
    assert not coarse["correct"], coarse["numbers"]
    assert coarse["numbers"]["frame_luma_mse"] > LIMITS["frame_luma_mse"]


@pytest.mark.parametrize("planes", ["all", "chroma", "444"])
def test_state_left_unchanged_fails(monkeypatch, planes):
    """``444``: the chroma planes of a 4:4:4 stream."""
    from selkies_tpu.engine.h264_encoder import H264EncoderSession
    band_step = H264EncoderSession._band_step
    first = 5 if planes == "all" else 6

    def broken(self, band_rows):
        step = band_step(self, band_rows)

        def keep_reference(frame, prev, sent, fnum, ry, ru, rv, *rest):
            kept = [jnp.copy(p) for p in (ry, ru, rv)][first - 5:]
            out = step(frame, prev, sent, fnum, ry, ru, rv, *rest)
            return out[:first] + tuple(kept) + out[8:]
        return keep_reference

    monkeypatch.setattr(H264EncoderSession, "_band_step", broken)
    out = run_cell("h264-1080p60", "motion",
                   "444" if planes == "444" else "420")
    assert not out["correct"], out["check"]


def test_half_the_stripes_left_out_fails(monkeypatch):
    from selkies_tpu.engine.encoder import JpegEncoderSession
    finalize = JpegEncoderSession.finalize_stream

    def half(self, out, force_all=False):
        for i, chunk in enumerate(finalize(self, out, force_all)):
            if i % 2 == 0:
                yield chunk

    monkeypatch.setattr(JpegEncoderSession, "finalize_stream", half)
    out = run_cell("jpeg-1080p60", "motion")
    assert not out["correct"], out["check"]


@pytest.mark.parametrize("config_name", ["h264-1080p60", "jpeg-1080p60"])
def test_altered_answer_fails(monkeypatch, config_name):
    if config_name.startswith("h264"):
        from selkies_tpu.engine.h264_encoder import H264EncoderSession as S
    else:
        from selkies_tpu.engine.encoder import JpegEncoderSession as S
    chunk = S._chunk

    def altered(self, out, i, *rest):
        c = chunk(self, out, i, *rest)
        if out["frame_id"] % 5 == 3:
            body = bytearray(c.payload)
            for k in range(len(body) // 2, len(body) // 2 + 8):
                body[k] ^= 0x5A
            c = dataclasses.replace(c, payload=bytes(body))
        return c

    monkeypatch.setattr(S, "_chunk", altered)
    out = run_cell(config_name, "motion")
    assert not out["correct"], out["check"]
