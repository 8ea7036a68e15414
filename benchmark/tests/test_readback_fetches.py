"""The reader of the program's counter ``readback.fetches``
(``metrics/readback_fetches.py``): the median per frame in the window,
and nothing from a program that does not count it."""

import pytest

from tests.test_waits import _ctx, _read, hand_built


def _with_fetches(per_frame):
    tls = hand_built()
    for tl, n in zip(tls, per_frame):
        tl["counts"]["readback.fetches"] = n
    return tls


@pytest.mark.parametrize("per_frame,want", [
    ((1, 1, 17), 1),            # the third frame begins after the window
    ((17, 1, 1), 9),
    ((3, 17, 1), 10),
])
def test_median_fetches_per_frame_in_the_window(per_frame, want):
    assert _read("readback_fetches", _ctx(_with_fetches(per_frame))) == want


def test_nothing_to_read_without_the_counter():
    assert _read("readback_fetches", _ctx(hand_built())) is None
