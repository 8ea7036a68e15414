"""A browser-shaped WebSocket client, run as a child process of the harness.

``python client.py <ws-url>`` speaks the handshake a browser speaks
(``MODE websockets`` -> ``server_settings`` -> ``SETTINGS,{json}`` ->
``START_VIDEO``), ACKs every new frame id, and stamps every video stripe
with ``time.monotonic()`` on arrival: the same ``CLOCK_MONOTONIC`` the
server process reads. Each stripe's record goes to stdout at once: ``<d``
arrival seconds, ``<I`` length, then the message's first ``HEAD`` bytes
(its header); the messages themselves stay here until a line on stdin
ends the session, and then follow in arrival order, each as ``<I``
length and bytes, after an end record of length ``END``. So the pipe
carries a few bytes per stripe while the window runs, and a busy parent
never stalls the socket. It never imports JAX, so the chip stays with
the parent.
"""

from __future__ import annotations

import asyncio
import json
import struct
import sys
import time

import aiohttp

RECORD = struct.Struct("<dI")
LENGTH = struct.Struct("<I")
#: header bytes sent with each record (the 0x04 header is 10, 0x03's 6)
HEAD = 10
END = 0xFFFFFFFF
#: binary opcodes that carry video stripes (0x03 JPEG, 0x04 H.264)
VIDEO_OPS = (0x03, 0x04)
#: the frame id's place in either stripe header: big-endian u16 at byte 2
FID = struct.Struct(">H")


async def session(url: str, stop: asyncio.Event, kept: list) -> None:
    out = sys.stdout.buffer
    async with aiohttp.ClientSession() as http:
        async with http.ws_connect(url, max_msg_size=0) as ws:
            greeting = await ws.receive_str(timeout=120)
            if greeting != "MODE websockets":
                raise RuntimeError(f"greeting was {greeting[:60]!r}")
            hello = await ws.receive_str(timeout=120)
            if not hello.startswith("server_settings "):
                raise RuntimeError(f"second message was {hello[:60]!r}")
            await ws.send_str("SETTINGS," + json.dumps({"framerate": 60}))
            await ws.send_str("START_VIDEO")
            acked = None
            while not stop.is_set():
                msg = await ws.receive()
                if msg.type == aiohttp.WSMsgType.TEXT:
                    continue
                if msg.type != aiohttp.WSMsgType.BINARY:
                    raise RuntimeError(f"websocket ended: {msg.type}")
                t = time.monotonic()
                data = msg.data
                if not data or data[0] not in VIDEO_OPS:
                    continue
                out.write(RECORD.pack(t, len(data)) + bytes(data[:HEAD]).ljust(
                    HEAD, b"\0"))
                out.flush()
                kept.append(data)
                fid = FID.unpack_from(data, 2)[0]
                if fid != acked:
                    acked = fid
                    await ws.send_str(f"CLIENT_FRAME_ACK {fid}")


async def main(url: str) -> None:
    loop = asyncio.get_running_loop()
    stop = asyncio.Event()
    loop.add_reader(sys.stdin.fileno(), stop.set)
    kept: list = []
    task = asyncio.ensure_future(session(url, stop, kept))
    waiter = asyncio.ensure_future(stop.wait())
    await asyncio.wait({task, waiter}, return_when=asyncio.FIRST_COMPLETED)
    if task.done():
        waiter.cancel()
        task.result()           # raises what ended the session early
        return
    task.cancel()
    try:
        await task
    except asyncio.CancelledError:
        pass
    out = sys.stdout.buffer
    out.write(RECORD.pack(0.0, END))
    for data in kept:
        out.write(LENGTH.pack(len(data)))
        out.write(data)
    out.flush()


if __name__ == "__main__":
    asyncio.run(main(sys.argv[1]))
