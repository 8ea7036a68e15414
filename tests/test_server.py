"""HTTP core + WS service tests with a fake capture (protocol-level client
simulators — the test strategy SURVEY.md §4 says the reference lacks)."""

import asyncio
import base64
import json

from aiohttp import WSMsgType

from selkies_tpu import protocol as P
from selkies_tpu.engine.types import CaptureSettings, EncodedChunk
from selkies_tpu.input.backends import NullBackend
from selkies_tpu.input.handler import InputHandler
from selkies_tpu.server.core import CentralizedStreamServer
from selkies_tpu.server.ws_service import WebSocketsService
from selkies_tpu.settings import AppSettings


class FakeCapture:
    """Emits one JPEG-ish chunk per start/idr; no TPU, no threads."""

    def __init__(self):
        self._cb = None
        self._settings = None
        self._capturing = False
        self.fid = 0
        self.idr_requests = 0
        self.encoded_fps = 42.0
        self._callback = None

    def start_capture(self, cb, settings):
        self._cb = self._callback = cb
        self._settings = settings
        self._capturing = True
        self.emit()

    def stop_capture(self):
        self._capturing = False

    def is_capturing(self):
        return self._capturing

    def request_idr_frame(self):
        self.idr_requests += 1
        if self._capturing:
            self.emit()

    def update_framerate(self, fps): ...
    def update_video_bitrate(self, kbps): ...
    def update_tunables(self, **kw): ...
    def update_capture_region(self, x, y, w, h): ...
    def set_cursor_callback(self, cb): self.cursor_cb = cb

    def emit(self, n=1):
        did = self._settings.display_id if self._settings else ":0"
        for _ in range(n):
            self._cb(EncodedChunk(
                payload=b"\xff\xd8FAKEJPEG\xff\xd9", frame_id=self.fid,
                stripe_y=0, width=64, height=64, is_idr=True,
                output_mode="jpeg", display_id=did))
            self.fid += 1


def make_app(env=None, capture_cls=FakeCapture, **fields):
    s = AppSettings.parse([], env or {})
    for k, v in fields.items():
        s.set_server(k, v)
    fake = capture_cls()
    handler = InputHandler(backend=NullBackend())
    svc = WebSocketsService(s, input_handler=handler,
                            capture_factory=lambda: fake)
    server = CentralizedStreamServer(s)
    server.register_service("websockets", svc)
    return server, svc, fake, handler


async def test_status_and_health(client_factory):
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    r = await c.get("/api/status")
    body = await r.json()
    assert r.status == 200 and body["mode"] == "websockets"
    r = await c.get("/api/health")
    assert (await r.json())["ok"] is True


async def test_basic_auth_and_viewonly(client_factory):
    server, svc, fake, _ = make_app(
        enable_basic_auth=True, basic_auth_user="u",
        basic_auth_password="pw", viewonly_password="vo")
    c = await client_factory(server)
    assert (await c.get("/api/status")).status == 401
    hdr = {"Authorization": "Basic " + base64.b64encode(b"u:pw").decode()}
    r = await c.get("/api/status", headers=hdr)
    assert r.status == 200 and (await r.json())["role"] == "full"
    hdr = {"Authorization": "Basic " + base64.b64encode(b"u:vo").decode()}
    r = await c.get("/api/status", headers=hdr)
    assert (await r.json())["role"] == "viewonly"
    hdr = {"Authorization": "Basic " + base64.b64encode(b"u:nope").decode()}
    assert (await c.get("/api/status", headers=hdr)).status == 401


async def test_master_token_bearer(client_factory):
    server, *_ = make_app(enable_basic_auth=True, basic_auth_user="u",
                          basic_auth_password="pw", master_token="tok123")
    c = await client_factory(server)
    r = await c.get("/api/status",
                    headers={"Authorization": "Bearer tok123"})
    assert r.status == 200 and (await r.json())["role"] == "full"
    assert (await c.get(
        "/api/status", headers={"Authorization": "Bearer bad"})).status == 401


async def test_ws_handshake_and_video(client_factory):
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    assert (await ws.receive_str()) == "MODE websockets"
    settings_msg = await ws.receive_str()
    assert settings_msg.startswith("server_settings ")
    payload = json.loads(settings_msg.split(" ", 1)[1])
    assert payload["settings"]["framerate"]["value"] == 60
    assert payload["features"]["resize"] is True

    await ws.send_str("START_VIDEO")
    got_binary = None
    for _ in range(10):
        msg = await ws.receive(timeout=5)
        if msg.type == WSMsgType.BINARY and msg.data[0] == P.OP_JPEG:
            got_binary = msg.data
            break
        if msg.type == WSMsgType.TEXT:
            continue
    assert got_binary is not None
    flags, fid, y = P.unpack_jpeg_header(got_binary)
    assert got_binary[6:8] == b"\xff\xd8"
    await ws.close()


async def test_keyframe_request_reaches_capture(client_factory):
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("START_VIDEO")
    await asyncio.sleep(0.1)
    before = fake.idr_requests
    await ws.send_str("REQUEST_KEYFRAME")
    await asyncio.sleep(0.1)
    assert fake.idr_requests > before
    await ws.close()


async def test_input_verbs_reach_backend(client_factory):
    server, svc, fake, handler = make_app()
    backend = handler.backend
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("kd,65")
    await ws.send_str("m,100,200")
    await ws.send_str("mb,1,1")
    await ws.send_str("ku,65")
    await asyncio.sleep(0.2)
    assert ("key", 65, True) in backend.events
    assert ("motion", 100, 200) in backend.events
    assert ("button", 1, True) in backend.events
    assert ("key", 65, False) in backend.events
    await ws.close()


async def test_viewonly_client_cannot_inject(client_factory):
    server, svc, fake, handler = make_app(
        enable_basic_auth=True, basic_auth_user="u",
        basic_auth_password="pw", viewonly_password="vo")
    backend = handler.backend
    c = await client_factory(server)
    hdr = {"Authorization": "Basic " + base64.b64encode(b"u:vo").decode()}
    ws = await c.ws_connect("/api/websockets", headers=hdr)
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("kd,65")
    await ws.send_str("REQUEST_KEYFRAME")   # allowed for viewers
    await asyncio.sleep(0.2)
    assert ("key", 65, True) not in backend.events
    await ws.close()


async def test_second_full_client_demoted_without_collab(client_factory):
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws1 = await c.ws_connect("/api/websockets")
    await ws1.receive_str(); await ws1.receive_str()
    await asyncio.sleep(0.6)  # reconnect debounce window
    ws2 = await c.ws_connect("/api/websockets")
    await ws2.receive_str(); await ws2.receive_str()
    roles = sorted(cl.role for cl in svc.clients.values())
    assert roles == ["full", "viewonly"]
    await ws1.close(); await ws2.close()


async def test_settings_verb_applies_and_rejects(client_factory):
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str('SETTINGS,{"framerate": 30, "master_token": "evil", "video_crf": 999}')
    msg = await ws.receive_str()
    assert msg.startswith("settings_applied ")
    applied = json.loads(msg.split(" ", 1)[1])
    assert applied == {"framerate": 30}
    assert svc.settings.framerate == 30
    assert svc.settings.master_token == ""
    await ws.close()


async def test_resize_updates_geometry(client_factory):
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("r,2560x1440")
    msg = await ws.receive_str()
    payload = json.loads(msg.split(" ", 1)[1])
    assert payload["displays"][0]["width"] == 2560
    assert svc.display_geometry[":0"] == (2560, 1440)
    await ws.close()


async def test_upload_and_download(tmp_path, client_factory):
    server, svc, fake, _ = make_app(file_transfer_dir=str(tmp_path))
    c = await client_factory(server)
    data1, data2 = b"A" * 1000, b"B" * 500
    r = await c.post("/api/upload", data=data1, headers={
        "X-Upload-Name": "test.bin", "X-Upload-Offset": "0",
        "X-Upload-Total": str(len(data1) + len(data2))})
    assert (await r.json())["complete"] is False
    r = await c.post("/api/upload", data=data2, headers={
        "X-Upload-Name": "test.bin", "X-Upload-Offset": str(len(data1)),
        "X-Upload-Total": str(len(data1) + len(data2))})
    assert (await r.json())["complete"] is True
    assert (tmp_path / "test.bin").read_bytes() == data1 + data2
    r = await c.get("/api/files")
    assert (await r.json())["files"][0]["name"] == "test.bin"
    r = await c.get("/api/files/test.bin")
    assert await r.read() == data1 + data2


async def test_upload_unicode_filename_percent_encoded(tmp_path,
                                                       client_factory):
    """The JS client percent-encodes X-Upload-Name (headers are Latin-1
    only); the server must decode it back to the real filename."""
    import urllib.parse
    server, svc, fake, _ = make_app(file_transfer_dir=str(tmp_path))
    c = await client_factory(server)
    name = "r\u00e9sum\u00e9 \u4e2d\u6587.pdf"
    r = await c.post("/api/upload", data=b"hello", headers={
        "X-Upload-Name": urllib.parse.quote(name),
        "X-Upload-Offset": "0", "X-Upload-Total": "5"})
    assert r.status == 200, await r.text()
    assert (tmp_path / name).read_bytes() == b"hello"


async def test_upload_path_traversal_rejected(tmp_path, client_factory):
    server, *_ = make_app(file_transfer_dir=str(tmp_path))
    c = await client_factory(server)
    r = await c.post("/api/upload", data=b"x", headers={
        "X-Upload-Name": "../../etc/passwd", "X-Upload-Offset": "0"})
    assert r.status == 400


async def test_metrics_endpoint(client_factory):
    server, *_ = make_app()
    c = await client_factory(server)
    r = await c.get("/api/metrics")
    text = await r.text()
    assert r.status == 200 and "# TYPE" in text


async def test_gzip_control_roundtrip(client_factory):
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("_gz,1")
    big = {"framerate": 24, "pad": "x" * 2000}
    framed = P.maybe_compress_text("SETTINGS," + json.dumps(big))
    assert isinstance(framed, bytes)
    await ws.send_bytes(framed)
    msg = await ws.receive()
    # reply may itself be gzip'd now that the client negotiated _gz
    text = (P.decompress_control(msg.data)
            if msg.type == WSMsgType.BINARY else msg.data)
    assert "framerate" in text and svc.settings.framerate == 24
    await ws.close()


async def test_viewonly_settings_do_not_mutate_server(client_factory):
    """A view-only client sending SETTINGS must not steer the shared stream
    (round-1 verdict: viewer-authority hole)."""
    server, svc, fake, _ = make_app(
        enable_basic_auth=True, basic_auth_user="u",
        basic_auth_password="pw", viewonly_password="vo")
    c = await client_factory(server)
    hdr = {"Authorization": "Basic " + base64.b64encode(b"u:vo").decode()}
    ws = await c.ws_connect("/api/websockets", headers=hdr)
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str('SETTINGS,{"framerate": 30}')
    msg = await ws.receive_str()
    assert json.loads(msg.split(" ", 1)[1]) == {}
    assert svc.settings.framerate == 60
    await ws.close()


async def test_malformed_input_verbs_do_not_disconnect(client_factory):
    """Garbage verb args must be tolerated, not tear down the WS
    (round-1 advisor finding)."""
    server, svc, fake, handler = make_app()
    backend = handler.backend
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("kd,notanumber")
    await ws.send_str("js,b,0")          # missing fields
    await ws.send_str("m,")              # empty args
    await ws.send_str("kd,65")           # connection still alive and working
    await asyncio.sleep(0.2)
    assert ("key", 65, True) in backend.events
    assert not ws.closed
    await ws.close()


async def test_static_web_client_served(client_factory):
    server, *_ = make_app()
    server.register_static()
    c = await client_factory(server)
    r = await c.get("/")
    body = await r.text()
    assert r.status == 200 and "selkies-client.js" in body
    r = await c.get("/selkies-client.js")
    assert r.status == 200 and "SelkiesClient" in await r.text()
    # addon surfaces (reference addons/selkies-dashboard + touch gamepad)
    r = await c.get("/dashboard/")
    assert r.status == 200 and "postMessage" in await r.text()
    r = await c.get("/touch-gamepad/universalTouchGamepad.js")
    assert r.status == 200 and "getGamepads" in await r.text()


async def test_cursor_broadcast_and_late_joiner(client_factory):
    """XFixes cursor updates broadcast as cursor,{json}; late joiners get
    the current cursor at handshake."""
    import numpy as np
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    rgba = np.zeros((8, 8, 4), np.uint8); rgba[..., 3] = 255
    svc._on_cursor({"rgba": rgba, "xhot": 2, "yhot": 3, "serial": 9})
    msg = await ws.receive_str()
    assert msg.startswith("cursor,")
    body = json.loads(msg.split(",", 1)[1])
    assert body["xhot"] == 2 and body["png_b64"]
    # second client sees the cursor right after server_settings
    await asyncio.sleep(0.6)  # reconnect debounce
    ws2 = await c.ws_connect("/api/websockets")
    await ws2.receive_str(); await ws2.receive_str()
    msg2 = await ws2.receive_str()
    assert msg2.startswith("cursor,")
    await ws.close(); await ws2.close()


async def test_secure_token_mode(client_factory):
    """secure_api: WS requires a minted token; /api/tokens mints them
    (reference /api/tokens + secure-mode gate, selkies.py:4516-4550)."""
    server, svc, fake, _ = make_app(
        secure_api=True, enable_basic_auth=True,
        basic_auth_user="u", basic_auth_password="pw")
    c = await client_factory(server)
    hdr = {"Authorization": "Basic " + base64.b64encode(b"u:pw").decode()}
    # no token -> connection refused with 4401
    ws = await c.ws_connect("/api/websockets", headers=hdr)
    await ws.receive()
    assert ws.close_code == 4401
    # mint a viewonly token and use it
    r = await c.post("/api/tokens", json={"role": "viewonly"}, headers=hdr)
    assert r.status == 200
    tok = (await r.json())["token"]
    await asyncio.sleep(0.6)   # reconnect debounce
    ws = await c.ws_connect(f"/api/websockets?token={tok}", headers=hdr)
    assert (await ws.receive_str()) == "MODE websockets"
    assert [cl.role for cl in svc.clients.values()] == ["viewonly"]
    # token list is redacted
    r = await c.get("/api/tokens", headers=hdr)
    body = await r.json()
    assert body["tokens"][0]["token"].endswith("…")
    await ws.close()


async def test_stats_include_device_telemetry(client_factory):
    server, svc, fake, _ = make_app(stats_interval_s=0.2)
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    import gzip as _gz
    for _ in range(20):
        msg = await asyncio.wait_for(ws.receive(), 5)
        text = msg.data
        if msg.type == WSMsgType.BINARY and msg.data[0] == P.OP_GZ_CONTROL:
            text = _gz.decompress(msg.data[1:]).decode()
        if isinstance(text, str) and text.startswith("system_stats"):
            stats = json.loads(text.split(" ", 1)[1])
            assert "devices" in stats
            assert stats["devices"][0]["platform"] == "cpu"
            break
    else:
        raise AssertionError("no system_stats seen")
    await ws.close()


async def test_multiseat_displays_route_per_client(client_factory):
    """tpu_seats>1: displays seat0..N-1 are advertised; each client views
    its ?display= pin and receives only that seat's chunks."""
    server, svc, fake, _ = make_app(tpu_seats=2)
    c = await client_factory(server)
    ws0 = await c.ws_connect("/api/websockets?display=seat0")
    await ws0.receive_str()
    payload = json.loads((await ws0.receive_str()).split(" ", 1)[1])
    assert [d["id"] for d in payload["displays"]] == ["seat0", "seat1"]
    await asyncio.sleep(0.6)
    ws1 = await c.ws_connect("/api/websockets?display=seat1")
    await ws1.receive_str(); await ws1.receive_str()
    await ws0.send_str("START_VIDEO")
    await ws1.send_str("START_VIDEO")
    await asyncio.sleep(0.1)
    # the custom factory stands in for the sharded capture; emit per-seat
    for seat in (0, 1):
        fake._cb(EncodedChunk(
            payload=b"\xff\xd8SEAT%d\xff\xd9" % seat, frame_id=seat,
            stripe_y=0, width=64, height=64, is_idr=True,
            output_mode="jpeg", display_id=f"seat{seat}"))
    got0 = got1 = None
    for _ in range(12):
        m = await asyncio.wait_for(ws0.receive(), 3)
        if m.type == WSMsgType.BINARY and m.data[0] == P.OP_JPEG:
            got0 = m.data; break
    for _ in range(12):
        m = await asyncio.wait_for(ws1.receive(), 3)
        if m.type == WSMsgType.BINARY and m.data[0] == P.OP_JPEG:
            got1 = m.data; break
    assert got0 and b"SEAT0" in got0
    assert got1 and b"SEAT1" in got1
    await ws0.close(); await ws1.close()


async def test_lifecycle_hooks_fire(client_factory, tmp_path):
    marker = tmp_path / "connected"
    marker2 = tmp_path / "disconnected"
    server, svc, fake, _ = make_app(
        run_after_connect=f"touch {marker}",
        run_after_disconnect=f"touch {marker2}")
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    for _ in range(40):
        if marker.exists():
            break
        await asyncio.sleep(0.05)
    assert marker.exists()
    await ws.close()
    for _ in range(40):
        if marker2.exists():
            break
        await asyncio.sleep(0.05)
    assert marker2.exists()


async def test_request_clipboard_pushes_to_clients(client_factory):
    server, svc, fake, handler = make_app()
    handler.backend.clipboard = (b"remote text", "text/plain")
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("REQUEST_CLIPBOARD")
    for _ in range(10):
        msg = await asyncio.wait_for(ws.receive_str(), 5)
        if msg.startswith("clipboard,"):
            assert base64.b64decode(msg.split(",", 1)[1]) == b"remote text"
            break
    else:
        raise AssertionError("no clipboard push")
    await ws.close()


async def test_recording_tap_and_stats_csv(client_factory, tmp_path):
    rec = tmp_path / "rec.mjpeg"
    csvp = tmp_path / "stats.csv"
    server, svc, fake, _ = make_app(
        recording_path=str(rec), stats_csv_path=str(csvp),
        stats_interval_s=0.2)
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("START_VIDEO")
    await asyncio.sleep(0.3)
    fake.emit(3)
    await asyncio.sleep(0.5)
    assert rec.exists() and rec.read_bytes().startswith(b"\xff\xd8")
    assert csvp.exists()
    lines = csvp.read_text().splitlines()
    assert lines[0].startswith("ts,cpu_percent")
    assert len(lines) >= 2
    await ws.close()


async def test_computer_use_api(client_factory):
    server, svc, fake, handler = make_app(enable_computer_use=True)
    backend = handler.backend
    c = await client_factory(server)
    r = await c.post("/api/computer_use",
                     json={"action": "click", "x": 10, "y": 20, "button": 1})
    assert (await r.json())["ok"] is True
    assert ("motion", 10, 20) in backend.events
    assert ("button", 1, True) in backend.events
    r = await c.post("/api/computer_use", json={"action": "type", "text": "hi"})
    assert r.status == 200
    assert ("key", ord("h"), True) in backend.events
    r = await c.post("/api/computer_use", json={"action": "nope"})
    assert r.status == 400
    # screenshot requires an active capture with frames; FakeCapture has no
    # screenshot() -> 503 is the honest degraded answer
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("START_VIDEO")
    await asyncio.sleep(0.2)
    r = await c.get("/api/screenshot")
    assert r.status == 503
    await ws.close()


import pytest as _pytest

_JS_FILES = (
    ("selkies_tpu", "web", "selkies-client.js"),
    ("selkies_tpu", "web", "lib", "protocol.js"),
    ("selkies_tpu", "web", "lib", "keysyms.js"),
    ("selkies_tpu", "web", "lib", "audio.js"),
    ("selkies_tpu", "web", "lib", "input.js"),
    ("selkies_tpu", "web", "lib", "upload.js"),
    ("selkies_tpu", "web", "lib", "video.js"),
    ("selkies_tpu", "web", "lib", "video-worker.js"),
    ("selkies_tpu", "web", "lib", "stripe-core.js"),
    ("addons", "universal-touch-gamepad", "universalTouchGamepad.js"),
    ("addons", "selkies-dashboard", "index.html"),
)


@_pytest.mark.parametrize("parts", _JS_FILES,
                          ids=[p[-1] for p in _JS_FILES])
def test_client_js_delimiters_balanced(parts):
    """No JS engine exists in this image, so guard the shipped client and
    JS addons against gross syntax damage: with strings/comments stripped,
    every bracket must balance and nest correctly."""
    import pathlib

    path = pathlib.Path(__file__).parent.parent.joinpath(*parts)
    raw = path.read_text()
    if path.suffix == ".html":      # check only the inline script body
        raw = "".join(raw.split("<script>")[1:]).split("</script>")[0]

    # state machine: comments, '…'/"…" strings, template literals with
    # nested ${ code } (a regex can't do this — `//` inside a template
    # URL must NOT count as a comment)
    out = []
    mode = [["code", 0]]               # stack of [kind, brace_depth]
    i, n = 0, len(raw)
    while i < n:
        kind = mode[-1][0]
        c = raw[i]
        nxt = raw[i + 1] if i + 1 < n else ""
        if kind == "code":
            if c == "/" and nxt == "/":
                j = raw.find("\n", i)
                i = n if j < 0 else j
                continue
            if c == "/" and nxt == "*":
                j = raw.find("*/", i + 2)
                i = n if j < 0 else j + 2
                continue
            if c in "'\"`":
                mode.append([c, 0])
                i += 1
                continue
            if c == "{":
                mode[-1][1] += 1
            elif c == "}":
                if mode[-1][1] == 0 and len(mode) > 1:
                    mode.pop()         # end of a template ${ }
                    i += 1
                    continue
                mode[-1][1] -= 1
            out.append(c)
            i += 1
        else:                          # inside a string/template
            if c == "\\":
                i += 2
                continue
            if c == kind:
                mode.pop()
                i += 1
                continue
            if kind == "`" and c == "$" and nxt == "{":
                mode.append(["code", 0])
                i += 2
                continue
            i += 1
    src = "".join(out)

    pairs = {")": "(", "]": "[", "}": "{"}
    stack = []
    for i, ch in enumerate(src):
        if ch in "([{":
            stack.append((ch, i))
        elif ch in pairs:
            assert stack, f"unmatched {ch!r} at offset {i}"
            top, _ = stack.pop()
            assert top == pairs[ch], \
                f"mismatched {ch!r} at offset {i} (open {top!r})"
    assert not stack, f"unclosed {stack[-1]!r}"
    if parts[-1] != "selkies-client.js":
        return
    # the client features must be present somewhere in the module graph
    # (entry + lib/ modules; test_web_client.py checks the graph itself)
    web = pathlib.Path(__file__).parent.parent / "selkies_tpu" / "web"
    corpus = "".join(p.read_text() for p in sorted(web.rglob("*.js")))
    for needle in ("js,c,", "js,b,", "js,a,", "getGamepads",
                   "X-Upload-Name", "touchstart",
                   # RTC transport path (server ICE-lite offer -> answer)
                   "RTCPeerConnection", "HELLO client", "SESSION server",
                   "createDataChannel", "setRemoteDescription",
                   # worker-decode / track-generator rendering path
                   "MediaStreamTrackGenerator", "VideoTrackGenerator",
                   "transferControlToOffscreen"):
        assert needle in corpus, needle


def test_gpu_stats_drm_sysfs_chain(tmp_path):
    """The DRM sysfs backfill reports AMD gauges and skips devices the
    NVML/nvidia-smi stages already covered (reference gpu_stats.py
    chain; neither NVIDIA path exists in this image, so sysfs is the
    live stage)."""
    from selkies_tpu.server import gpu_stats as G

    # fake /sys/class/drm with one amdgpu card and one intel card
    card0 = tmp_path / "card0" / "device"
    card0.mkdir(parents=True)
    (card0 / "vendor").write_text("0x1002\n")
    (card0 / "gpu_busy_percent").write_text("37\n")
    (card0 / "mem_info_vram_used").write_text(str(512 * 2**20))
    (card0 / "mem_info_vram_total").write_text(str(8192 * 2**20))
    card1 = tmp_path / "card1" / "device"
    card1.mkdir(parents=True)
    (card1 / "vendor").write_text("0x8086\n")
    # connector nodes (card0-DP-1) must be ignored
    (tmp_path / "card0-DP-1").mkdir()

    gpus = G.get_gpus(drm_root=str(tmp_path))
    assert len(gpus) == 2
    amd = next(g for g in gpus if g.vendor == "amd")
    assert amd.load_percent == 37.0
    assert amd.memory_used_mb == 512.0
    assert amd.memory_total_mb == 8192.0
    assert amd.source == "drm-sysfs"
    intel = next(g for g in gpus if g.vendor == "intel")
    assert intel.load_percent is None
    payload = G.gpu_stats_payload(drm_root=str(tmp_path))
    assert isinstance(payload, list) and payload[0]["vendor"] in ("amd",
                                                                  "intel")


async def test_file_transfer_role_and_direction_gating(tmp_path,
                                                       client_factory):
    """VERDICT r3 weak 7: downloads must be role-gated like uploads, and
    the reference's file_transfers direction list must be honoured
    (reference stream_server.py:980,1171)."""
    (tmp_path / "f.bin").write_bytes(b"secret")
    server, *_ = make_app(
        enable_basic_auth=True, basic_auth_user="u",
        basic_auth_password="pw", viewonly_password="vo",
        file_transfer_dir=str(tmp_path))
    c = await client_factory(server)
    full = {"Authorization": "Basic " + base64.b64encode(b"u:pw").decode()}
    vo = {"Authorization": "Basic " + base64.b64encode(b"u:vo").decode()}
    # full role: default directions allow both
    assert (await c.get("/api/files", headers=full)).status == 200
    assert (await c.get("/api/files/f.bin", headers=full)).status == 200
    # view-only: 403 on index, download AND upload by default
    assert (await c.get("/api/files", headers=vo)).status == 403
    assert (await c.get("/api/files/f.bin", headers=vo)).status == 403
    r = await c.post("/api/upload", data=b"x", headers={
        **vo, "X-Upload-Name": "x.bin", "X-Upload-Offset": "0",
        "X-Upload-Total": "1"})
    assert r.status == 403
    # ...unless explicitly opened to the view-only role
    server2, *_ = make_app(
        enable_basic_auth=True, basic_auth_user="u",
        basic_auth_password="pw", viewonly_password="vo",
        file_transfer_dir=str(tmp_path), viewonly_file_transfers="download")
    c2 = await client_factory(server2)
    assert (await c2.get("/api/files/f.bin", headers=vo)).status == 200
    # direction list: upload-only server denies downloads for everyone
    server3, *_ = make_app(file_transfer_dir=str(tmp_path),
                           file_transfers="upload")
    c3 = await client_factory(server3)
    assert (await c3.get("/api/files", )).status == 403
    assert (await c3.get("/api/files/f.bin")).status == 403


async def test_keyframe_targets_requesting_display_only(client_factory):
    """REQUEST_KEYFRAME (and the fresh-join IDR) must hit only the
    requesting client's display, not storm every capture (VERDICT r3
    weak 7)."""
    s = AppSettings.parse([], {})
    fakes = []

    def factory():
        f = FakeCapture()
        fakes.append(f)
        return f

    handler = InputHandler(backend=NullBackend())
    svc = WebSocketsService(s, input_handler=handler,
                            capture_factory=factory)
    server = CentralizedStreamServer(s)
    server.register_service("websockets", svc)
    c = await client_factory(server)

    ws1 = await c.ws_connect("/api/websockets")
    await ws1.receive_str(); await ws1.receive_str()
    await ws1.send_str("START_VIDEO")
    await asyncio.sleep(0.1)
    ws2 = await c.ws_connect("/api/websockets?display=display2")
    await ws2.receive_str(); await ws2.receive_str()
    await ws2.send_str("START_VIDEO")
    await asyncio.sleep(0.2)
    assert len(fakes) == 2
    base = [f.idr_requests for f in fakes]
    await ws2.send_str("REQUEST_KEYFRAME")
    await asyncio.sleep(0.2)
    assert fakes[1].idr_requests > base[1], "target display must IDR"
    assert fakes[0].idr_requests == base[0], \
        "other display must NOT be IDR-stormed"
    await ws1.close()
    await ws2.close()


async def test_mic_disabled_notice_once(client_factory):
    """0x02 frames with the mic disabled get ONE MICROPHONE_DISABLED
    (reference parity) so the client UI can stop capturing."""
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive()                      # MODE
    await ws.receive()                      # server_settings
    await ws.send_bytes(b"\x02" + b"\x00" * 32)
    await ws.send_bytes(b"\x02" + b"\x00" * 32)
    got = []
    try:
        while True:
            msg = await asyncio.wait_for(ws.receive(), timeout=1.5)
            if msg.type == WSMsgType.TEXT and "MICROPHONE" in msg.data:
                got.append(msg.data)
    except asyncio.TimeoutError:
        pass
    assert got == ["MICROPHONE_DISABLED"]


async def test_window_manager_swap_safelisted(client_factory, tmp_path,
                                              monkeypatch):
    """SETTINGS window_manager execs only safelisted WMs (a client-
    writable exec must never run arbitrary binaries)."""
    import os as _os
    bin_dir = tmp_path / "bin"
    bin_dir.mkdir()
    log = tmp_path / "wm.log"
    s = bin_dir / "openbox"
    s.write_text(f"#!/bin/sh\necho \"$@\" > {log}\n")
    s.chmod(0o755)
    evil = bin_dir / "evilbin"
    evil.write_text(f"#!/bin/sh\necho evil > {log}.evil\n")
    evil.chmod(0o755)
    monkeypatch.setenv("PATH", f"{bin_dir}:{_os.environ['PATH']}")

    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive(); await ws.receive()
    await ws.send_str('SETTINGS,{"window_manager": "evilbin"}')
    await ws.send_str('SETTINGS,{"window_manager": "openbox"}')
    deadline = asyncio.get_event_loop().time() + 5
    while asyncio.get_event_loop().time() < deadline and not log.exists():
        await asyncio.sleep(0.05)
    assert log.exists() and "--replace" in log.read_text()
    assert not (tmp_path / "wm.log.evil").exists()


async def test_rtc_config_file_pushes_to_clients(client_factory, tmp_path):
    """rtc_config_file edits reach connected clients as an rtc_config
    push (reference RTCConfigFileMonitor end-to-end)."""
    import os as _os
    path = tmp_path / "rtc.json"
    server, svc, fake, _ = make_app(rtc_config_file=str(path))
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive(); await ws.receive()
    # monitor polls at 1 s; write after connect so the push targets us
    path.write_text(json.dumps({"iceServers": [{"urls": ["stun:x"]}]}))
    _os.chmod(path, 0o600)
    got = None
    deadline = asyncio.get_event_loop().time() + 6
    while asyncio.get_event_loop().time() < deadline:
        try:
            msg = await asyncio.wait_for(ws.receive(), timeout=2)
        except asyncio.TimeoutError:
            continue
        if msg.type == WSMsgType.TEXT and msg.data.startswith("rtc_config"):
            got = msg.data
            break
    assert got is not None
    cfg = json.loads(got.split(",", 1)[1])
    assert cfg["iceServers"][0]["urls"] == ["stun:x"]
    await ws.close()


async def test_cold_start_system_msg(client_factory):
    """Starting a capture pushes a 'preparing encoder' system_msg so a
    minutes-long first compile isn't a silent black screen (VERDICT r3
    weak 4)."""
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive(); await ws.receive()
    await ws.send_str("START_VIDEO")
    got = None
    deadline = asyncio.get_event_loop().time() + 5
    while asyncio.get_event_loop().time() < deadline and got is None:
        try:
            msg = await asyncio.wait_for(ws.receive(), timeout=2)
        except asyncio.TimeoutError:
            continue
        if msg.type == WSMsgType.TEXT and msg.data.startswith("system_msg"):
            got = msg.data
    assert got is not None and "preparing encoder" in got
    await ws.close()


def test_prometheus_label_escaping():
    """Satellite (ISSUE 2): '"' and '\\' (and newlines) in label values
    must be escaped per the Prometheus text exposition spec, or the
    /api/metrics output is unparseable."""
    from selkies_tpu.server import metrics
    metrics.clear()
    metrics.set_gauge("esc_test_gauge", 1.0,
                      {"path": 'C:\\tmp "quoted"\nnext'})
    text = metrics.render_prometheus()
    assert ('esc_test_gauge{path="C:\\\\tmp \\"quoted\\"\\nnext"} 1.0'
            in text)
    # escaped output stays one physical line per sample
    sample = [ln for ln in text.splitlines() if "esc_test_gauge{" in ln]
    assert len(sample) == 1
    metrics.clear()


async def test_relay_death_metrics():
    """Satellite (ISSUE 2): relay death must be visible at /api/metrics
    (counter + alive gauge), not only as a bench fallback string."""
    from selkies_tpu.server import metrics
    from selkies_tpu.server.relay import VideoRelay

    def _gauge(text, name):
        for ln in text.splitlines():
            if ln.startswith(name + " ") or ln.startswith(name + "{"):
                return float(ln.rsplit(" ", 1)[1])
        return None

    def _counter(text):
        return _gauge(text, "selkies_relay_deaths_total") or 0.0

    async def _failing_send(data):
        raise ConnectionError("peer gone")

    deaths_before = _counter(metrics.render_prometheus())
    relay = VideoRelay(_failing_send, display=":0")
    relay.start()
    alive_started = _gauge(metrics.render_prometheus(),
                           "selkies_relay_alive")
    relay.offer(P.pack_jpeg_stripe(1, 0, b"\xff\xd8payload\xff\xd9"))
    for _ in range(50):
        await asyncio.sleep(0.01)
        if relay.dead:
            break
    assert relay.dead
    text = metrics.render_prometheus()
    assert _counter(text) == deaths_before + 1
    assert _gauge(text, "selkies_relay_alive") == alive_started - 1
    # a second death verdict on the same relay (control path + sender
    # task can both conclude it) must not double-count
    relay.mark_dead()
    assert _counter(metrics.render_prometheus()) == deaths_before + 1
    # close() of an already-dead relay must not double-release
    await relay.close()
    assert _gauge(metrics.render_prometheus(),
                  "selkies_relay_alive") == alive_started - 1


async def test_relay_clean_close_is_not_a_death():
    from selkies_tpu.server import metrics
    from selkies_tpu.server.relay import VideoRelay

    def _counter(text):
        for ln in text.splitlines():
            if ln.startswith("selkies_relay_deaths_total "):
                return float(ln.rsplit(" ", 1)[1])
        return 0.0

    sent = []

    async def _send(data):
        sent.append(data)

    before = _counter(metrics.render_prometheus())
    relay = VideoRelay(_send, display=":0")
    relay.start()
    relay.offer(P.pack_jpeg_stripe(2, 0, b"\xff\xd8ok\xff\xd9"))
    await asyncio.sleep(0.05)
    await relay.close()
    assert sent
    assert _counter(metrics.render_prometheus()) == before


async def test_sessions_endpoint_live_stats(client_factory):
    """ISSUE 4 acceptance: GET /api/sessions returns live per-session
    ACK RTT, client fps, and drop counts for a streaming WS client."""
    import time as _time

    from selkies_tpu.obs import qoe as _qoe
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("START_VIDEO")
    got = None
    for _ in range(10):
        msg = await asyncio.wait_for(ws.receive(), 5)
        if msg.type == WSMsgType.BINARY and msg.data[0] == P.OP_JPEG:
            got = msg.data
            break
    assert got is not None
    _, fid, _ = P.unpack_jpeg_header(got)
    await ws.send_str(f"CLIENT_FRAME_ACK,{fid}")
    await ws.send_str("_f,58.5")
    await asyncio.sleep(0.2)

    r = await c.get("/api/sessions")
    assert r.status == 200
    doc = await r.json()
    assert doc["count"] == 1
    s = doc["sessions"][0]
    assert s["kind"] == "ws" and s["seat"] == ":0"
    assert s["video_active"] is True
    assert s["frames_sent"] >= 1
    assert s["client_fps"] == 58.5
    assert s["ack_rtt_ms"] >= 0.0
    assert s["dropped_frames"] == 0 and s["drop_rate"] == 0.0
    assert s["qoe_score"] is not None and s["qoe_score"] > 50

    r = await c.get("/api/sessions?verbose=1")
    v = (await r.json())["sessions"][0]
    assert v["ack"]["acked"] >= 1 and v["ack"]["p50_ms"] is not None
    assert v["relay"]["sent_bytes"] > 0
    assert "backpressure" in v and v["raddr"]

    # the session disappears from the registry on disconnect
    await ws.close()
    await asyncio.sleep(0.2)
    assert all(st.kind != "ws"
               for st in _qoe.registry.sessions()), "session leaked"
    _ = _time  # silence unused in case of skip paths


async def test_sessions_endpoint_role_gated(client_factory):
    server, *_ = make_app(
        enable_basic_auth=True, basic_auth_user="u",
        basic_auth_password="pw", viewonly_password="vo")
    c = await client_factory(server)
    vo = {"Authorization": "Basic " + base64.b64encode(b"u:vo").decode()}
    assert (await c.get("/api/sessions", headers=vo)).status == 403
    full = {"Authorization": "Basic " + base64.b64encode(b"u:pw").decode()}
    assert (await c.get("/api/sessions", headers=full)).status == 200


async def test_stalled_client_fails_qoe_check_and_records_collapse(
        client_factory):
    """ISSUE 4 acceptance: a stalled client (frames sent, never ACKed)
    drives the qoe health check to failed and a qoe_collapse incident
    into the flight recorder."""
    import time as _time

    from selkies_tpu.obs import health as _health
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("START_VIDEO")
    await asyncio.sleep(0.2)
    client = next(iter(svc.clients.values()))
    assert client.qoe is not None and client.qoe.frames_sent >= 1
    # simulate the stall: a frame sent 10 s ago with no ACK since
    client.qoe.note_sent(4242, _time.monotonic() - 10.0)
    before = [e["kind"] for e in _health.engine.recorder.snapshot()]
    r = await c.get("/api/health?verbose=1")
    body = await r.json()
    assert body["checks"]["qoe"]["status"] == "failed"
    assert "qoe" in body["failing"] and body["ready"] is False
    kinds = [e["kind"] for e in body["incidents"]]
    assert kinds.count("qoe_collapse") == before.count("qoe_collapse") + 1
    await ws.close()


async def test_relay_sent_and_dropped_metrics_per_display():
    """Satellite (ISSUE 4): FrameRelay sent_bytes/dropped_frames reach
    /api/metrics as per-display counters, not just the debug
    snapshot."""
    from selkies_tpu.server import metrics
    from selkies_tpu.server.relay import VideoRelay

    def _counter(text, name, display):
        needle = f'{name}{{display="{display}"}} '
        for ln in text.splitlines():
            if ln.startswith(needle):
                return float(ln.rsplit(" ", 1)[1])
        return 0.0

    gate = asyncio.Event()

    async def _send(data):
        await gate.wait()

    text0 = metrics.render_prometheus()
    sent0 = _counter(text0, "selkies_relay_sent_bytes_total", ":qoet")
    drop0 = _counter(text0, "selkies_relay_dropped_frames_total", ":qoet")
    relay = VideoRelay(_send, display=":qoet")
    relay.start()
    big = P.pack_jpeg_stripe(1, 0, b"\xff\xd8" + b"x" * (3 << 20))
    relay.offer(big)                      # picked up by the blocked sender
    await asyncio.sleep(0.05)
    relay.offer(big)                      # queued: 3 MiB
    relay.offer(big)                      # 6 MiB > 4 MiB floor -> drop
    assert relay.dropped_frames == 1
    gate.set()
    for _ in range(100):
        await asyncio.sleep(0.01)
        if relay.sent_bytes >= 2 * len(big):
            break
    await relay.close()
    text = metrics.render_prometheus()
    assert _counter(text, "selkies_relay_sent_bytes_total", ":qoet") \
        == sent0 + 2 * len(big)
    assert _counter(text, "selkies_relay_dropped_frames_total", ":qoet") \
        == drop0 + 1


async def test_trace_endpoint_carries_qoe_lane(client_factory):
    """Backpressure windows overlay the /api/trace timeline as a qoe
    lane (the PR-2 Perfetto view shows WHEN a seat was paused)."""
    from selkies_tpu.obs import qoe as _qoe
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("START_VIDEO")
    await asyncio.sleep(0.2)
    client = next(iter(svc.clients.values()))
    import time as _time
    client.qoe.backpressure_begin(_time.monotonic() - 0.5)
    client.qoe.backpressure_end(_time.monotonic())
    r = await c.get("/api/trace")
    doc = await r.json()
    lanes = [e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "thread_name"]
    assert "qoe" in lanes
    assert any(e.get("ph") == "X"
               and str(e.get("name", "")).startswith("backpressure")
               for e in doc["traceEvents"])
    _ = _qoe
    await ws.close()


async def test_perf_endpoint_reports_steps_and_occupancy(client_factory):
    """GET /api/perf (ISSUE 6): static step cost table + occupancy over
    the live trace ring, JSON-round-trippable; ?profile=1 is full-role
    gated and answers null with no capture on disk."""
    from selkies_tpu.obs import perf as _perf
    from selkies_tpu.trace import tracer
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    _perf.registry.record_analysis(
        "h264.i_step[srvtest]",
        cost={"flops": 1e6, "bytes accessed": 8e6},
        memory={"argument_size_in_bytes": 1, "output_size_in_bytes": 2,
                "temp_size_in_bytes": 3}, backend="cpu")
    tracer.enable(capacity=16)
    try:
        tl = tracer.frame_begin(":perft")
        tracer.bind(tl, 5)
        with tracer.span("packetize", tl):
            await asyncio.sleep(0.002)
        tracer.frame_end(":perft", 5)
        r = await c.get("/api/perf")
        assert r.status == 200
        doc = await r.json()
        names = [s["name"] for s in doc["perf"]["steps"]]
        assert "h264.i_step[srvtest]" in names
        step = doc["perf"]["steps"][names.index("h264.i_step[srvtest]")]
        assert step["roofline_ms"] is None          # no peak for a cpu
        assert doc["occupancy"]["frames"] >= 1
        assert "packetize" in doc["occupancy"]["critical_path"]
        assert doc["tracing"] is True
        # an earlier test's jax.profiler capture (test_obs' on-demand
        # profile round-trip) leaves the module-global last_trace_dir
        # set — this assertion is about the NO-capture answer, so
        # isolate it from suite ordering
        from selkies_tpu.obs.profiler import profiler as _prof_session
        saved_dir, _prof_session.last_trace_dir = \
            _prof_session.last_trace_dir, None
        try:
            r = await c.get("/api/perf?profile=1")
            assert r.status == 200
            assert (await r.json())["profile"] is None  # no capture yet
        finally:
            _prof_session.last_trace_dir = saved_dir
    finally:
        tracer.disable()
        tracer.clear()
        _perf.registry.clear()


async def test_relay_send_span_attaches_to_frame_timeline():
    """The ws.send stage lands on the frame's trace timeline by id."""
    from selkies_tpu.server.relay import VideoRelay
    from selkies_tpu.trace import tracer

    async def _send(data):
        await asyncio.sleep(0)

    tracer.enable(capacity=16)
    try:
        tl = tracer.frame_begin(":0")
        tracer.bind(tl, 42)
        relay = VideoRelay(_send, display=":0")
        relay.start()
        relay.offer(P.pack_jpeg_stripe(42, 0, b"\xff\xd8x\xff\xd9"))
        for _ in range(50):
            await asyncio.sleep(0.01)
            if any(s[0] == "ws.send" for s in tl.spans):
                break
        await relay.close()
        assert any(s[0] == "ws.send" for s in tl.spans)
    finally:
        tracer.disable()
        tracer.clear()


# --------------------------------------------------------------------------
# Glass-to-glass plane (ISSUE 7): clock exchange, frame-timing join, SLO
# surface, and the malformed-command hardening.
# --------------------------------------------------------------------------

def _pc_ms():
    import time as _time
    return _time.perf_counter_ns() / 1e6


async def _sync_clock(ws, n=3):
    """Run n CLIENT_CLOCK exchanges; the test process IS the client, so
    its 'client clock' is the server's perf_counter (offset ~0) and
    mapped timestamps can be asserted against perf_counter directly."""
    for i in range(n):
        await ws.send_str(f"CLIENT_CLOCK ping,{i},{_pc_ms():.3f}")
        reply = await asyncio.wait_for(ws.receive_str(), 5)
        assert reply.startswith("server_clock ")
        await ws.send_str(
            f"CLIENT_CLOCK sample,{reply.split(' ', 1)[1]},{_pc_ms():.3f}")
    await asyncio.sleep(0.05)


async def test_clock_sync_exchange_and_sessions_export(client_factory):
    """CLIENT_CLOCK ping -> server_clock reply -> sample feeds the
    session's estimator; quality lands in /api/sessions?verbose=1."""
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await _sync_clock(ws)
    r = await c.get("/api/sessions?verbose=1")
    v = (await r.json())["sessions"][0]
    assert v["clock"]["synced"] is True
    assert v["clock"]["samples"] == 3
    # same process, same physical clock: offset must read ~0
    assert abs(v["clock"]["offset_ms"]) < 50.0
    assert v["clock"]["rtt_min_ms"] is not None
    await ws.close()


async def test_frame_timing_joins_g2g_trace_and_slo(client_factory):
    """The tentpole round-trip: a timed frame becomes a per-session g2g
    sample, client-lane spans on /api/trace (with the frame envelope
    extended past ws.send), and a g2g SLO event."""
    from selkies_tpu.obs import slo as _slo
    from selkies_tpu.trace import tracer
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await _sync_clock(ws)
    g2g_before = _slo.engine.get("g2g").good_total

    tracer.enable(capacity=32)
    try:
        await ws.send_str("START_VIDEO")
        got = None
        for _ in range(10):
            msg = await asyncio.wait_for(ws.receive(), 5)
            if msg.type == WSMsgType.BINARY and msg.data[0] == P.OP_JPEG:
                got = msg.data
                break
        assert got is not None
        _, fid, _ = P.unpack_jpeg_header(got)
        recv = _pc_ms()
        # the fake capture emitted before tracing was on for this frame;
        # give the frame a closed timeline the client spans can join
        tl = tracer.frame_begin(":0")
        tracer.bind(tl, fid)
        tracer.frame_end(":0", fid)
        t1_closed = tl.t1_ns
        await ws.send_str(
            f"CLIENT_FRAME_TIMING {fid}:{recv:.2f}:{recv + 2.5:.2f}:"
            f"{recv + 4.0:.2f}")
        await asyncio.sleep(0.1)

        # g2g sample in the session snapshot
        r = await c.get("/api/sessions?verbose=1")
        v = (await r.json())["sessions"][0]
        assert v["g2g"]["n"] == 1 and v["g2g"]["p99_ms"] > 0
        assert v["g2g_p99_ms"] == v["g2g"]["p99_ms"]

        # client lane on the trace doc, envelope extended to present
        r = await c.get("/api/trace")
        doc = await r.json()
        lanes = [e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "thread_name"]
        assert "client" in lanes
        names = {e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
        assert {"net", "client.decode", "client.present"} <= names
        assert tl.t1_ns > t1_closed, "frame envelope must extend to present"

        # one g2g SLO event recorded (well under the 250 ms budget)
        assert _slo.engine.get("g2g").good_total == g2g_before + 1
        r = await c.get("/api/slo")
        slo_doc = await r.json()
        assert slo_doc["status"] == "ok"
        assert {d["name"] for d in slo_doc["slos"]} == {"fps", "g2g", "qoe"}
    finally:
        tracer.disable()
        tracer.clear()
    await ws.close()


async def test_slo_feed_skips_idle_sessions(client_factory):
    """Damage gating means a static desktop legitimately delivers no
    frames; an fps/qoe bad event per tick for such a session would burn
    the error budget — and page — on a perfectly healthy system."""
    import time as _time

    from selkies_tpu.obs import slo as _slo
    server, svc, fake, _ = make_app(stats_interval_s=0.1)
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str("START_VIDEO")
    for _ in range(10):
        msg = await asyncio.wait_for(ws.receive(), 5)
        if msg.type == WSMsgType.BINARY and msg.data[0] == P.OP_JPEG:
            break
    await ws.send_str("_f,1.0")            # a terrible client fps
    await asyncio.sleep(0.05)
    cc = next(iter(svc.clients.values()))
    fps_slo = _slo.engine.get("fps")
    # age the delivery stamp past the idle horizon: nobody is painting
    cc.qoe.last_send_mono = _time.monotonic() - 10.0
    before = (fps_slo.good_total, fps_slo.bad_total)
    await asyncio.sleep(0.35)              # >= 2 stats ticks
    assert (fps_slo.good_total, fps_slo.bad_total) == before
    # fresh delivery re-enables the feed (and records the bad fps)
    fake.emit()
    await asyncio.sleep(0.35)
    assert fps_slo.bad_total > before[1]
    await ws.close()


async def test_api_slo_flips_under_g2g_regression(client_factory):
    """ISSUE 7 acceptance: the burn-rate verdict flips failed under an
    injected g2g regression — injected event stamps, zero sleeps."""
    import time as _time

    from selkies_tpu.obs import slo as _slo
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    r = await c.get("/api/slo")
    doc = await r.json()
    assert doc["status"] == "ok"
    g2g = next(d for d in doc["slos"] if d["name"] == "g2g")
    assert g2g["burn_fast"] is None        # no events yet

    now = _time.monotonic()
    _slo.engine.record("g2g", good=True, n=50, now=now - 10.0)
    _slo.engine.record("g2g", good=False, n=450, now=now)
    r = await c.get("/api/slo")
    doc = await r.json()
    assert doc["status"] == "failed"
    g2g = next(d for d in doc["slos"] if d["name"] == "g2g")
    assert g2g["status"] == "failed"
    assert g2g["burn_fast"] > g2g["burn_threshold"]
    assert g2g["budget_remaining"] == 0.0
    # the slo health check carries the verdict + a slo_burn incident
    r = await c.get("/api/health?verbose=1")
    body = await r.json()
    assert body["checks"]["slo"]["status"] == "failed"
    assert "g2g" in body["checks"]["slo"]["reason"]
    assert any(e["kind"] == "slo_burn" for e in body["incidents"])


async def test_malformed_protocol_messages_counted_and_dropped(
        client_factory):
    """ISSUE 7 satellite: any malformed ACK/timing/clock/stats token
    increments selkies_protocol_errors_total{kind} and is dropped; the
    receive loop survives and keeps answering."""
    from selkies_tpu.server import metrics
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()

    cases = [
        ("CLIENT_FRAME_ACK,notanint", "client_frame_ack"),
        ("CLIENT_FRAME_TIMING abc:1:2:3", "client_frame_timing"),
        ("CLIENT_FRAME_TIMING 1:2:3", "client_frame_timing"),
        ("CLIENT_FRAME_TIMING ", "client_frame_timing"),
        ("CLIENT_FRAME_TIMING 1:nan:2:3", "client_frame_timing"),
        ("CLIENT_FRAME_TIMING 7:1:2:3;8:9", "client_frame_timing"),
        ("CLIENT_CLOCK ping,1", "client_clock"),
        ("CLIENT_CLOCK sample,1,2,3", "client_clock"),
        ("CLIENT_CLOCK bogus,1,2,3", "client_clock"),
        ("CLIENT_CLOCK ping,1,inf", "client_clock"),
        ("CLIENT_STATS notjson", "client_stats"),
        ("CLIENT_STATS [1,2]", "client_stats"),
        # deep nesting raises RecursionError, not ValueError — it must
        # be counted+dropped, not tear down the receive loop
        ("CLIENT_STATS " + "[" * 100_000, "client_stats"),
        # a well-formed sample that echoes no outstanding ping: the
        # estimator must not trust client-fabricated server stamps
        ("CLIENT_CLOCK sample,77,1.0,2.0,3.0,4.0", "client_clock"),
    ]
    before = {k: metrics.counter_value("selkies_protocol_errors_total",
                                       {"kind": k})
              for _, k in cases}
    for text, _kind in cases:
        await ws.send_str(text)
    # a valid exchange after the garbage proves the loop survived
    await ws.send_str(f"CLIENT_CLOCK ping,99,{_pc_ms():.3f}")
    reply = await asyncio.wait_for(ws.receive_str(), 5)
    assert reply.startswith("server_clock 99,")

    from collections import Counter
    want = Counter(k for _, k in cases)
    for kind, n in want.items():
        got = metrics.counter_value("selkies_protocol_errors_total",
                                    {"kind": kind})
        assert got == before[kind] + n, (kind, got, before[kind], n)
    await ws.close()


async def test_client_stats_surface_in_sessions(client_factory):
    """CLIENT_STATS (decoder queue depth, dropped decodes) lands in the
    verbose session snapshot — and hostile fields do not."""
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    ws = await c.ws_connect("/api/websockets")
    await ws.receive_str(); await ws.receive_str()
    await ws.send_str('CLIENT_STATS {"decode_queue": 7, '
                      '"dropped_decodes": 3, "draw_fps": 58.5, '
                      '"evil": "x", "huge": 1e300}')
    await asyncio.sleep(0.1)
    r = await c.get("/api/sessions?verbose=1")
    v = (await r.json())["sessions"][0]
    assert v["client"] == {"decode_queue": 7.0, "dropped_decodes": 3.0,
                           "draw_fps": 58.5}
    await ws.close()


# --------------------------------------------------------- compile plane

async def test_prewarm_endpoint_reports_lattice_and_gate(client_factory):
    """GET /api/prewarm (ISSUE 8): the worker's lattice snapshot, the
    ladder's deferral state, and the artifact outcome in one panel —
    and the ladder is actually gated on the worker."""
    server, svc, fake, _ = make_app()
    c = await client_factory(server)
    r = await c.get("/api/prewarm")
    body = await r.json()
    assert r.status == 200 and body["enabled"] is True
    w = body["worker"]
    assert w["lattice_size"] >= 2          # base + downscale target
    assert w["pending"] + w["warmed"] == w["lattice_size"]
    geoms = {e["geometry"] for e in w["entries"]}
    assert "1920x1080" in geoms and "960x540" in geoms
    assert body["ladder"] == {"deferred": None,
                              "deferred_transitions": 0,
                              "gated": True, "level": 0}
    # the gate is the worker's: a cold downscale rung defers
    assert server.ladder.gate.query("downscale", +1) == "cold"
    assert server.ladder.gate.query("fps", +1) == "warm"
    # prewarm health check registered (ok while warming)
    r = await c.get("/api/health?verbose=1")
    checks = (await r.json())["checks"]
    assert checks["prewarm"]["status"] == "ok"


async def test_prewarm_disabled_by_setting(client_factory):
    server, svc, fake, _ = make_app(enable_prewarm=False)
    c = await client_factory(server)
    body = await (await c.get("/api/prewarm")).json()
    assert body["enabled"] is False and body["worker"] is None
    assert server.ladder.gate is None


# ------------------------------------------------- ACK stall vs a host stall
def _ack_waiting_client(svc, cid=1):
    """A video client whose last frame has gone unacknowledged for longer
    than the ACK stall rule allows."""
    import time
    import types

    from selkies_tpu.server import ws_service
    c = types.SimpleNamespace(
        id=cid, display=":0", video_active=True, paused=False, qoe=None,
        last_sent_id=69, last_ack_id=68,
        last_ack_time=time.monotonic() - ws_service.ACK_STALL_S - 3)
    svc.clients[cid] = c
    return c


async def test_ack_stall_pauses_a_client_that_stays_silent(monkeypatch):
    from selkies_tpu.server import ws_service
    monkeypatch.setattr(ws_service, "ACK_RECHECK_S", 0.05)
    _, svc, _, _ = make_app()
    c = _ack_waiting_client(svc)
    await svc._check_ack_stalls()
    assert c.paused


async def test_ack_read_after_a_stall_of_the_server_does_not_pause(
        monkeypatch):
    """The server process stalled (its loop blocked) with the client's
    ACK unread: the ACK that the loop reads as it catches up, within the
    recheck, keeps the client streaming."""
    import time

    from selkies_tpu.server import ws_service
    monkeypatch.setattr(ws_service, "ACK_RECHECK_S", 0.2)
    _, svc, _, _ = make_app()
    c = _ack_waiting_client(svc)

    async def ack_arrives():
        await asyncio.sleep(0.02)
        c.last_ack_id, c.last_ack_time = 69, time.monotonic()

    task = asyncio.create_task(ack_arrives())
    await svc._check_ack_stalls()
    await task
    assert not c.paused
