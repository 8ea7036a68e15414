"""Per-frame span tracing with latency attribution.

The bench round that motivated this package measured 0.42 fps at ~790 ms
p99 glass-to-glass and could not say WHICH stage (capture, CSC, DCT+quant,
entropy, packetize, ws send) ate the budget — ``server/metrics.py`` only
carries endpoint-level gauges. This package is the attribution layer:

- :mod:`.core` — a dependency-free, low-overhead span tracer: monotonic
  spans correlated by frame id, thread/task-safe via ``contextvars``, a
  fixed-size ring of completed frame timelines, and near-zero cost when
  disabled (the disabled ``span()`` path is one flag check returning a
  shared singleton — no allocation per frame);
- :mod:`.export` — Chrome trace-event JSON, loadable in Perfetto or
  ``chrome://tracing``;
- :mod:`.summary` — per-stage p50/p99 percentiles, fed into the
  ``server.metrics`` registry as ``selkies_stage_ms`` histograms;
- :mod:`.__main__` — offline CLI: ``python -m selkies_tpu.trace
  summarize <trace.json>``.

Everything here is stdlib-only: the CLI and exporter must run in images
with neither jax nor aiohttp installed (the CI lint job).

Stage names used across the repo (the bench breakdown contract): the
frame stages ``capture``, ``convert``, ``encode.dispatch``,
``encode.readback``, ``packetize``, ``fanout``, ``ws.send``, the work
span ``readback.copy`` nested in ``encode.readback``, the wait spans
(on ``<lane>.wait``) ``encode.probe_sync``, ``pipeline.admit``,
``pipeline.queue``, ``readback.device_wait``, ``readback.slice_wait``,
``fanout.hop`` and ``relay.queue`` — plus the ``ack`` instant. Counters:
``readback.bytes``, ``readback.fetches``.
"""

from .core import FrameTracer, FrameTimeline, tracer  # noqa: F401

#: the stages a frame passes through one after another: no two nest, so
#: their sum is the frame's attributed time
FRAME_STAGES = ("capture", "convert", "encode.dispatch", "encode.readback",
                "packetize", "fanout", "ws.send")

#: the spans that only wait, each on the wait lane of where it blocks
WAIT_STAGES = ("encode.probe_sync", "pipeline.admit", "pipeline.queue",
               "readback.device_wait", "readback.slice_wait", "fanout.hop",
               "relay.queue")

#: the repo-wide stage-name contract (bench reports every one of these,
#: zero-filled when a stage cannot occur in its loop, e.g. ws.send);
#: every name but the frame stages nests in one of them or lies between
STAGES = FRAME_STAGES + ("readback.copy",) + WAIT_STAGES
