"""Median per frame of the waits of its last stripe sent between its
bytes being ready and its send: wait span ``fanout.hop`` (the
finalizer's hand-off to the event loop) plus ``relay.queue`` (the relay's
queue until the pop for the send), ms. Layer: packetize, relay and
websocket (server/ws_service.py, server/relay.py)."""

from harness import layers, waits


def _last_ns(spans):
    hops = [s for s in spans if s["name"] == "fanout.hop"]
    queued = [s for s in spans if s["name"] == "relay.queue"]
    if not hops or not queued:
        return None
    return max(hops, key=waits.end)["dur_ns"] + \
        max(queued, key=waits.end)["dur_ns"]


def read(ctx):
    return layers.median(waits.per_frame(ctx, _last_ns))
