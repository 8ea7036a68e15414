"""Minimal-readback fetch (engine/readback.py, PERF.md lever 4) and the
stripe-granular fetch path (deep pipeline, ROADMAP 2)."""

import numpy as np

from selkies_tpu.engine.readback import (MIN_BUCKET, MIN_STRIPE_BUCKET,
                                         bucket_for, fetch_stream_bytes,
                                         fetch_stripe_bytes)


def test_bucket_ladder():
    assert bucket_for(0) == MIN_BUCKET
    assert bucket_for(1) == MIN_BUCKET
    assert bucket_for(MIN_BUCKET) == MIN_BUCKET
    assert bucket_for(MIN_BUCKET + 1) == 2 * MIN_BUCKET
    assert bucket_for(100_000) == 131072


def test_fetch_prefix_is_byte_identical():
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    full = rng.integers(0, 256, (4 * MIN_BUCKET,), dtype=np.uint8)
    dev = jnp.asarray(full)
    for total in (0, 1, 1000, MIN_BUCKET, MIN_BUCKET + 7,
                  3 * MIN_BUCKET, 4 * MIN_BUCKET):
        got = fetch_stream_bytes(dev, total)
        assert len(got) >= total
        assert np.array_equal(got[:total], full[:total]), total


def test_small_buffer_fetch_covers_request():
    import jax.numpy as jnp
    full = np.arange(100, dtype=np.uint8)
    got = fetch_stream_bytes(jnp.asarray(full), 50)
    # contract: AT LEAST the requested prefix, byte-identical (the host
    # path returns exactly the prefix; the device path rounds up)
    assert len(got) >= 50
    assert np.array_equal(got[:50], full[:50])


def test_fetch_stripe_arbitrary_ranges_byte_identical():
    """The stripe-streaming fetch: any (start, length) range equals the
    same slice of the full buffer — including ranges that straddle the
    bucketed device slice's clamp at the buffer tail."""
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    n = 4 * MIN_STRIPE_BUCKET
    full = rng.integers(0, 256, (n,), dtype=np.uint8)
    dev = jnp.asarray(full)
    cases = [(0, 0), (0, 1), (0, MIN_STRIPE_BUCKET), (17, 1000),
             (MIN_STRIPE_BUCKET - 1, 2), (n - 100, 100),
             (n - 1, 1), (n - MIN_STRIPE_BUCKET - 3, MIN_STRIPE_BUCKET),
             (1000, 3 * MIN_STRIPE_BUCKET)]
    for start, length in cases:
        got = fetch_stripe_bytes(dev, start, length)
        assert np.array_equal(got, full[start:start + length]), \
            (start, length)


def test_fetch_stripe_clamps_overlong_range():
    import jax.numpy as jnp
    full = np.arange(256, dtype=np.uint8)
    got = fetch_stripe_bytes(jnp.asarray(full), 200, 1000)
    assert np.array_equal(got, full[200:])


def test_fetch_stripe_seat_axis_preserved():
    """Multi-seat (S, out_cap) buffers slice along the minor axis."""
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    full = rng.integers(0, 256, (2, 2 * MIN_STRIPE_BUCKET), dtype=np.uint8)
    got = fetch_stripe_bytes(jnp.asarray(full), 123, 456)
    assert np.array_equal(got, full[:, 123:123 + 456])


# -- traced fetches: counted, the slice's wait apart from the copy -----------

def _traced_timeline():
    from selkies_tpu.trace import tracer
    tracer.enable(capacity=16)
    tl = tracer.frame_begin(":rb")
    tracer.bind(tl, 1)
    return tracer, tl


def test_traced_device_fetch_counts_the_buckets(monkeypatch):
    """On the device path (forced here: the CPU backend is host memory)
    ``readback.bytes`` is the sum of the buckets that crossed,
    ``readback.fetches`` the number of transfers, and each
    bucketed slice's wait is a ``readback.slice_wait`` on the lane's
    wait track."""
    import jax.numpy as jnp

    from selkies_tpu.engine import readback
    monkeypatch.setattr(readback, "_on_host", lambda arr: False)
    rng = np.random.default_rng(5)
    n = 8 * MIN_BUCKET
    full = rng.integers(0, 256, (n,), dtype=np.uint8)
    dev = jnp.asarray(full)
    tracer, tl = _traced_timeline()
    try:
        asks = [(None, 1000), (None, MIN_BUCKET + 1), (0, 300),
                (77, MIN_STRIPE_BUCKET + 5), (n - 10, 10)]
        for start, length in asks:
            if start is None:
                got = readback.fetch_stream_bytes(dev, length, tl=tl,
                                                  lane="slot1")
                assert np.array_equal(got[:length], full[:length])
            else:
                got = readback.fetch_stripe_bytes(dev, start, length, tl=tl,
                                                  lane="slot1")
                assert np.array_equal(got, full[start:start + length])
        buckets = [bucket_for(length) if start is None
                   else bucket_for(length, MIN_STRIPE_BUCKET)
                   for start, length in asks]
        assert tl.counts == {"readback.bytes": sum(buckets),
                             "readback.fetches": len(asks)}
        waits = [s for s in tl.spans if s[0] == "readback.slice_wait"]
        assert len(waits) == len(asks)
        assert {s[1] for s in waits} == {"slot1.wait"}
    finally:
        tracer.disable()
        tracer.clear()


def test_traced_host_fetch_counts_what_it_copies():
    """On the host path nothing is bucketed and no slice is waited for:
    the bytes counted are the bytes sliced, per seat row."""
    import jax.numpy as jnp
    full = np.arange(2 * 512, dtype=np.uint8).reshape(2, 512)
    tracer, tl = _traced_timeline()
    try:
        got = fetch_stripe_bytes(jnp.asarray(full), 100, 50, tl=tl)
        assert np.array_equal(got, full[:, 100:150])
        assert tl.counts == {"readback.bytes": 100, "readback.fetches": 1}
        assert tl.spans == []
    finally:
        tracer.disable()
        tracer.clear()
