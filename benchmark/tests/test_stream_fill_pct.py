"""The reader of the program's counters ``stream.bytes`` and
``stream.cap`` (``metrics/stream_fill_pct.py``): the fullest frame in
the window, and nothing from a program that does not count them."""

import pytest

from tests.test_waits import _ctx, _read, hand_built


def _with_fill(per_frame):
    tls = hand_built()
    for tl, (n, cap) in zip(tls, per_frame):
        tl["counts"].update({"stream.bytes": n, "stream.cap": cap})
    return tls


@pytest.mark.parametrize("per_frame,want", [
    # the third frame begins after the window
    (((100, 400), (300, 400), (400, 400)), 75.0),
    # the second frame encoded with a buffer grown to twice the first's
    (((300, 400), (500, 800), (1, 2)), 75.0),
    (((10, 400), (796, 800), (1, 2)), 99.5),
])
def test_largest_fill_in_the_window(per_frame, want):
    assert _read("stream_fill_pct", _ctx(_with_fill(per_frame))) \
        == pytest.approx(want)


def test_nothing_to_read_without_the_counters():
    assert _read("stream_fill_pct", _ctx(hand_built())) is None
