"""The traffic mixes and the byte counts, on the CPU at the cells' own
1920x1080 geometry (the scenes are host data; nothing here is timed)."""

import json
from pathlib import Path

import numpy as np
import pytest

from harness.desktop import Desktop
from harness.layers import load_module

BENCH = Path(__file__).resolve().parent.parent
W, H = 1920, 1080
SEED = 2**33 + 12345          # larger than 32 bits, as the driver's are


def scene(mix):
    return json.loads((BENCH / "traffic" / f"{mix}.json").read_text())


@pytest.fixture(scope="module")
def motion():
    return Desktop(scene("motion"), W, H, SEED)


@pytest.fixture(scope="module")
def typing():
    return Desktop(scene("typing"), W, H, SEED)


def mb_rows(a, b):
    rows = np.any(a != b, axis=(1, 2))
    return set(np.nonzero(rows)[0] // 16)


@pytest.mark.parametrize("mix", ["motion", "typing"])
def test_same_seed_same_frames_other_seed_other_frames(mix, motion, typing):
    d = motion if mix == "motion" else typing
    again = Desktop(scene(mix), W, H, SEED)
    other = Desktop(scene(mix), W, H, SEED + 1)
    for r in (0, 61, 1234, 20000):
        assert d.content_key(r) == again.content_key(r)
        assert np.array_equal(d.render(r), again.render(r))
    assert not np.array_equal(d.render(1234), other.render(1234))


def test_motion_changes_every_macroblock_at_every_update(motion):
    for r in (5, 300, 4567):
        assert motion.content_key(r) == r
        changed = np.zeros((1088, W), bool)       # the coded picture
        changed[:H] = np.any(motion.render(r - 1) != motion.render(r),
                             axis=2)
        per_mb = changed.reshape(68, 16, W // 16, 16).any(axis=(1, 3))
        # every macroblock that holds desktop pixels (the last row's
        # bottom half is the encoder's padding)
        assert per_mb.all()


def test_typing_keystroke_dirties_two_macroblock_rows(typing):
    text = typing._text
    checked = 0
    for k in range(1, 60):
        r = int(text.key_refresh[k - 1])
        pos_before = text.pos0 + text.keys_at(r - 1)
        pos_after = text.pos0 + text.keys_at(r)
        if pos_before // text.cols != pos_after // text.cols:
            continue                      # a wrap: the scroll test's case
        rows = mb_rows(typing.render(r - 1), typing.render(r))
        assert len(rows) == 2, (k, sorted(rows))
        checked += 1
    assert checked > 40


def test_typing_wrap_scrolls_the_whole_pane(typing):
    text = typing._text
    wraps = [int(r) for k, r in enumerate(text.key_refresh, start=1)
             if (text.pos0 + k) % text.cols == 0][:2]
    pane = set(range(text.y // 16, (text.y + text.rows * text.ch - 1) // 16
                     + 1))
    for r in wraps:
        # the keystroke that fills the line moves the caret to a new one
        rows = mb_rows(typing.render(r - 1), typing.render(r))
        assert rows == pane


def test_typing_update_rate_and_scroll_period(typing):
    keys = {typing.content_key(r) for r in range(60 * 60)}
    assert 5.5 <= len(keys) / 60 <= 8.5
    text = typing._text
    per_line = text.cols / (len(text.key_refresh) / text.key_refresh[-1]
                            * 60)
    assert 15 <= per_line <= 20       # seconds between scrolls


def test_byte_counts_at_1920x1088():
    h264 = load_module(BENCH / "counts" / "h264.py")
    jpeg = load_module(BENCH / "counts" / "jpeg.py")
    frame = 1920 * 1088 * 3                       # 6,266,880
    planes = 1920 * 1088 * 3 // 2                 # 3,133,440 (4:2:0)
    assert h264.bytes_needed(1920, 1088, intra=False, dirty_rows=68,
                             payload_bytes=100_000) \
        == 2 * frame + 2 * planes + 100_000 == 18_900_640
    assert h264.bytes_needed(1920, 1088, intra=True, dirty_rows=0,
                             payload_bytes=100_000) \
        == 2 * frame + planes + 100_000 == 15_767_200
    # two macroblock rows of a keystroke: 2 * 16 * 1920 px
    assert h264.bytes_needed(1920, 1088, intra=False, dirty_rows=2,
                             payload_bytes=1_000) \
        == 2 * frame + 2 * (2 * 16 * 1920 * 3 // 2) + 1_000 == 12_719_080
    assert jpeg.bytes_needed(1920, 1088, intra=False, dirty_rows=68,
                             payload_bytes=200_000) \
        == 2 * frame + 200_000 == 12_733_760
    # 4:4:4: the reference and the reconstruction at 3 bytes a pixel
    assert h264.bytes_needed(1920, 1088, intra=False, dirty_rows=68,
                             payload_bytes=100_000, chroma="444") \
        == 2 * frame + 2 * frame + 100_000 == 25_167_520
    assert h264.bytes_needed(1920, 1088, intra=True, dirty_rows=0,
                             payload_bytes=100_000, chroma="444") \
        == 2 * frame + frame + 100_000 == 18_900_640
    # JPEG keeps no reference: its count is the same at either sampling
    assert jpeg.bytes_needed(1920, 1088, intra=False, dirty_rows=68,
                             payload_bytes=200_000, chroma="444") \
        == 12_733_760
