"""The check on the committed 4:4:4 configuration, ``h264-1080p60-444``
(the reference's ``fullcolor`` H.264), on the CPU at small size: the
sound run passes, and the controls and the fault planted in the chroma
planes fail. ``test_check.py`` runs the same cases on the synthetic 4:4:4
variant of ``h264-1080p60`` (``tiny.config(name, "444")``); this module
runs them on the configuration file the cell ``h264-1080p60-444.motion``
runs, so an edit to that file is checked here too."""

import json

import jax
import jax.numpy as jnp
import pytest

from tests import tiny
from tests.test_check import BENCH, H264_444_CHROMA, LIMITS, SEED

NAME = "h264-1080p60-444"
#: the test's limits: ``test_check.py``'s, with its 4:4:4 H.264 limit on
#: the worst 16x16 chroma block
LIMITS_444 = {**LIMITS, "worst_chroma_mse": H264_444_CHROMA}


def load(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def run_cell(config=None):
    import run
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return run.execute(bench, tiny.cell(f"{NAME}.motion", NAME, "motion"),
                       config or tiny.config(NAME), tiny.scene("motion"),
                       SEED, 3.0, False, jax.devices(), limits=LIMITS_444)


def readings(specs, flags=None):
    import readings as r
    return r.measure(tiny.cell(f"{NAME}.motion", NAME, "motion"),
                     tiny.config(NAME), tiny.scene("motion"), 3.0, specs,
                     jax.devices(), LIMITS_444, flags)


def test_444_configuration_is_the_420_one_with_fullcolor():
    """``h264-1080p60-444`` is the reference's fullcolor setting: the
    deployment of ``h264-1080p60`` with that one setting changed, and the
    4:4:4 sampling promised."""
    base, full = load("h264-1080p60"), load(NAME)
    assert full["flags"] == {**base["flags"], "fullcolor": True}
    assert full["chroma"] == "444" and "chroma" not in base
    for k in ("codec", "coded", "assumed", "fidelity_control", "warmup",
              "trace_s"):
        assert full[k] == base[k], k


def test_tiny_config_keeps_the_444_promise():
    c = tiny.config(NAME)
    assert c["chroma"] == "444" and c["flags"]["fullcolor"] is True


def test_sound_run_is_correct():
    sound = run_cell()
    assert sound["correct"], sound["check"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert list(sound)[-1] == "check"
    assert set(sound["check"]) == set(LIMITS)


def test_stale_grey_and_halfchroma_controls_fail():
    """Grey and colour averaged to 4:2:0 keep the luma: only the chroma
    comparison catches them."""
    sound, stale, *colour = readings(
        [SEED, f"stale:{SEED + 1}", f"grey:{SEED + 2}",
         f"halfchroma:{SEED + 3}"])
    assert sound["correct"], sound["numbers"]
    assert sound["compiles_in_window"] == 0
    n = sound["numbers"]
    assert not stale["correct"]
    assert stale["numbers"]["worst_luma_mse"] > 3 * n["worst_luma_mse"]
    for g in colour:
        assert not g["correct"], (g["mode"], g["numbers"])
        assert g["numbers"]["worst_luma_mse"] <= LIMITS["worst_luma_mse"]
        assert g["numbers"]["worst_chroma_mse"] > 3 * n["worst_chroma_mse"]


def test_fidelity_control_fails():
    flags = tiny.config(NAME)["fidelity_control"]["flags"]
    (coarse,) = readings([SEED], flags)
    assert not coarse["correct"], coarse["numbers"]
    assert coarse["numbers"]["frame_luma_mse"] > LIMITS["frame_luma_mse"]


def test_chroma_left_unchanged_fails(monkeypatch):
    """The band step returns the chroma reference planes unchanged."""
    from selkies_tpu.engine.h264_encoder import H264EncoderSession
    band_step = H264EncoderSession._band_step

    def broken(self, band_rows):
        step = band_step(self, band_rows)

        def keep_chroma(frame, prev, sent, fnum, ry, ru, rv, *rest):
            kept = (jnp.copy(ru), jnp.copy(rv))
            out = step(frame, prev, sent, fnum, ry, ru, rv, *rest)
            return out[:6] + kept + out[8:]
        return keep_chroma

    monkeypatch.setattr(H264EncoderSession, "_band_step", broken)
    out = run_cell()
    assert not out["correct"], out["check"]
