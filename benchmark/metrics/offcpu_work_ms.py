"""Median per frame of the time the host's work spans spent neither
running nor in a traced wait, ms: over ``capture``, ``convert``,
``encode.dispatch``, ``packetize``, ``fanout`` and ``readback.copy``, the
sum of wall time less the waits nested in each (on its wait lane) less
its thread CPU time (``cpu_ns``). Time runnable but not run (the
interpreter lock, the CPU) or blocked untraced. A wait that runs on the
thread's CPU (the CPU backend computes on the waiting thread) makes it
read low, even below 0. Layer: host threads."""

from harness import layers, waits

WORK = ("capture", "convert", "encode.dispatch", "packetize", "fanout",
        "readback.copy")


def _offcpu_ns(spans):
    work = [s for s in spans if s["name"] in WORK and "cpu_ns" in s]
    if not work:
        return None
    return sum(s["dur_ns"] - waits.nested_wait_ns(s, spans) - s["cpu_ns"]
               for s in work)


def read(ctx):
    return layers.median(waits.per_frame(ctx, _offcpu_ns))
