"""Minimal host readback for the encoded-stream buffer (PERF.md lever
4: ship ~ceil(total_bits/8) bytes per frame, not the full out_cap).

A jitted program must return a static shape, so the device keeps the
full-capacity buffer; the HOST decides how much of it to fetch after the
tiny per-row length vector arrives: the smallest power-of-two bucket
covering the real byte total is sliced ON DEVICE (one cached jit per
bucket) and only that prefix crosses the link. At 1080p the capacity
readback is ~0.5 MB/frame; a typical P frame
fits in 32-64 KB, and an idle frame (no stripes sent) now fetches
nothing at all. Byte-identical to fetching everything — the slice is a
prefix; tests cover both encoders bit-exactly.

Range path (ROADMAP 2): :func:`fetch_stripe_bytes` slices an ARBITRARY
byte range on device (``dynamic_slice`` with a bucketed static length,
so the jit cache stays one-per-bucket). The single-seat sessions'
finalize makes ONE such fetch per frame, over the delivered stripes
from the first to the last (an H.264 band frame: one prefix fetch of
the band), cuts the stripes from the host copy and still yields them
one at a time: each device round trip costs far more than the bytes it
carries, so a fetch per stripe would set the frame's pace. The range
leaves out the clean stripes before the first delivered one, which a
prefix fetch would carry. Range fetches use a smaller bucket floor
than the whole-frame prefix: a damage-gated range can be one small
stripe.

Both fetch paths carry the ``readback.fetch`` fault point
(``slow``/``error``): an injected mid-pipeline readback death exercises
the ring-drain recovery path (``bench.py --chaos``).

Given the frame's trace timeline (keyword ``tl``, tracer on), a fetch
counts ``readback.bytes`` (what the transfer moves: the bucket, or the
whole buffer) and ``readback.fetches`` (one per transfer), and waits
for the bucketed slice on its own before the transfer completes
(``readback.slice_wait`` on ``<lane>.wait``): the slice is enqueued on
the device stream and can sit behind the next frame's step.
With the tracer off nothing is synced that the transfer would not sync.
"""

from __future__ import annotations

import functools

import numpy as np

from ..resilience import faults as _faults
from ..trace import tracer as _tracer

#: smallest whole-frame fetch; below this the dispatch RTT dominates
MIN_BUCKET = 32768

#: smallest range fetch (a damage-gated range can be one small stripe)
MIN_STRIPE_BUCKET = 4096


def _on_host(arr) -> bool:
    """True when the buffer already lives in host memory (cpu backend).
    Minimal readback exists to save the HOST LINK; on the cpu backend
    there is no link, and routing the fetch through a jitted slice
    would enqueue compute on the XLA stream — a pipelined fetch then
    serializes behind the next frame's step. ``np.asarray`` on a ready
    host buffer waits only for ITS producing computation, never the
    queue, so the deep pipeline's finalizer never contends with the
    capture thread's dispatches."""
    try:
        devs = arr.devices()
        return all(d.platform == "cpu" for d in devs)
    except Exception:
        return True     # plain numpy / unknown: host semantics


@functools.lru_cache(maxsize=64)
def _slice_fn(bucket: int):
    import jax
    # last-axis prefix: works for the single-seat (out_cap,) buffer AND
    # the seat-sharded (S, out_cap) buffer — slicing the minor axis
    # preserves the seat-axis sharding, so each device ships only its
    # own prefix
    return jax.jit(lambda d: d[..., :bucket])


@functools.lru_cache(maxsize=64)
def _stripe_slice_fn(bucket: int):
    import jax
    from jax import lax
    # traced start, static bucket length: one compile per bucket covers
    # every stripe offset (dynamic_slice clamps start so start+bucket
    # stays in range — the host caller compensates, see fetch_stripe)
    return jax.jit(lambda d, s: lax.dynamic_slice_in_dim(
        d, s, bucket, axis=d.ndim - 1))


def bucket_for(total: int, floor: int = MIN_BUCKET) -> int:
    b = floor
    while b < total:
        b *= 2
    return b


def _transfer(arr, tl, lane, extent: int | None = None,
              sync: bool = True) -> np.ndarray:
    """``np.asarray(arr)``: the device-to-host copy. With ``tl`` traced:
    counted (one fetch of ``extent`` bytes, the last-axis length that
    crosses, default all of it, per leading index) and, for a device
    slice (``sync``), its wait for the device apart from the copy."""
    if tl is None or not _tracer.enabled:
        return np.asarray(arr)
    width = int(arr.shape[-1])
    rows = int(arr.size) // width if width else 0
    _tracer.count(tl, "readback.bytes",
                  rows * (width if extent is None else extent))
    _tracer.count(tl, "readback.fetches", 1)
    if sync:
        # the copy is queued behind the slice first, as np.asarray alone
        # would: the wait below then adds no host round trip to it
        arr.copy_to_host_async()
        with _tracer.span("readback.slice_wait", tl, lane=lane, wait=True):
            arr.block_until_ready()
    return np.asarray(arr)


def fetch_stream_bytes(data_dev, total: int, *, tl=None,
                       lane=None) -> np.ndarray:
    """Fetch the first ``total`` bytes (along the last axis) of the
    device stream buffer, rounded up to a bucket so the jit cache stays
    tiny. ``tl``/``lane``: the frame's trace timeline and the span lane
    (module docstring)."""
    _faults.registry.perturb("readback.fetch")
    if total <= 0:
        return np.zeros(tuple(data_dev.shape[:-1]) + (0,), np.uint8)
    n = int(data_dev.shape[-1])
    if _on_host(data_dev):
        k = min(total, n)
        return _transfer(data_dev, tl, lane, k, sync=False)[..., :k]
    bucket = bucket_for(total)
    if bucket >= n:
        return _transfer(data_dev, tl, lane)
    return _transfer(_slice_fn(bucket)(data_dev), tl, lane)


def fetch_stripe_bytes(data_dev, start: int, length: int, *, tl=None,
                       lane=None) -> np.ndarray:
    """Fetch ``length`` bytes at ``start`` (along the last axis) — the
    finalizers' one fetch per frame. Byte-identical to the same range of a
    whole-prefix fetch; the bucketed device slice may over-fetch up to
    one bucket, never under. ``tl``/``lane`` as for
    :func:`fetch_stream_bytes`."""
    _faults.registry.perturb("readback.fetch")
    if length <= 0:
        return np.zeros(tuple(data_dev.shape[:-1]) + (0,), np.uint8)
    n = int(data_dev.shape[-1])
    start = max(0, int(start))
    length = min(int(length), n - start)
    if _on_host(data_dev):
        return _transfer(data_dev, tl, lane, length,
                         sync=False)[..., start:start + length]
    bucket = bucket_for(length, MIN_STRIPE_BUCKET)
    if bucket >= n:
        return _transfer(data_dev, tl, lane)[..., start:start + length]
    # dynamic_slice clamps start to n - bucket: fetch the clamped
    # window and re-offset on the host so the caller's range is exact
    eff = min(start, n - bucket)
    raw = _transfer(_stripe_slice_fn(bucket)(data_dev, eff), tl, lane)
    off = start - eff
    return raw[..., off:off + length]
