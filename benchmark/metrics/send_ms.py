"""Median per frame of the self times of spans ``packetize`` and
``ws.send``, ms. Layer: packetize, relay, WebSocket (server/relay.py,
server/ws_service.py)."""

from harness import layers


def read(ctx):
    return layers.median(
        layers.per_frame_self_ms(ctx, ("packetize", "ws.send")))
