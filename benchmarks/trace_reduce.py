"""From a profiler trace to numbers, with ``jax.profiler.ProfileData`` alone.

``load`` reads the ``.xplane.pb`` a traced window left behind into plain
lists: per device plane the events of its ``XLA Ops`` line, and the
benchmark's own host spans (``bench:<name>`` trace annotations, which the
profiler puts on the same clock). ``reduce`` turns those lists into device
busy time, self time per operation and the longest idle gaps by what the
host was doing. Both fail rather than report a trace that saw no device.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench:"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
#: The chip's trace names an operation by its whole HLO line:
#: ``%fusion.18 = s32[1025000]{0:T(1024)} fusion(...)``.
_HLO_LINE = re.compile(r"^%(\S+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def op_name(event_name: str) -> str:
    """``fusion.18 s32[1025000]``: the instruction's name and the shape (of
    its first result), which is how a reader finds a kernel by its name."""
    match = _HLO_LINE.match(event_name)
    if not match:
        return event_name
    return match.group(1) + (" " + match.group(2) if match.group(2) else "")


def load(trace_dir: str, platform: str = "tpu") -> dict:
    """``{"devices": {plane: [(name, start_ns, duration_ns)]}, "spans": [...]}``.

    On the chip the device planes are ``/device:TPU:<n>`` and their
    operations the ``XLA Ops`` line. A CPU rehearsal has no device plane: the
    host plane's events that carry an ``hlo_op`` stat stand in, so that the
    traced path can be driven end to end without a chip. A trace without
    device operations is an error either way."""
    import jax

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise RuntimeError(f"the profiler left no .xplane.pb under {trace_dir}")
    devices, spans = {}, []
    for plane in jax.profiler.ProfileData.from_file(found[0]).planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), int(e.start_ns), int(e.duration_ns)) for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], int(e.start_ns), int(e.duration_ns)))
                    elif platform != "tpu" and e.duration_ns and "hlo_op" in dict(e.stats):
                        devices.setdefault("/host:CPU (rehearsal)", []).append(
                            (e.name, int(e.start_ns), int(e.duration_ns)))
    if platform == "tpu" and "/device:TPU:0" not in devices:
        raise RuntimeError("the trace has no /device:TPU:0 plane with an 'XLA Ops' line")
    if not any(devices.values()):
        raise RuntimeError("the trace holds no device operation")
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def _merge(events):
    """Busy intervals: the union of the events' intervals, sorted."""
    merged = []
    for _, start, duration in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + duration)
        else:
            merged.append([start, start + duration])
    return merged


def _self_times(events):
    """Seconds and calls per operation name, children taken out of the
    operations that contain them (a ``while`` spans its body's operations)."""
    self_ns, calls, stack = {}, {}, []
    for name, start, duration in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + duration
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            self_ns[stack[-1][0]] -= min(end, stack[-1][1]) - start
        self_ns[name] = self_ns.get(name, 0) + duration
        calls[name] = calls.get(name, 0) + 1
        stack.append((name, end))
    return self_ns, calls


def reduce(loaded: dict, top: int = 10) -> dict:
    devices = loaded["devices"]
    busy_ns = [sum(end - start for start, end in _merge(events)) for events in devices.values()]
    # Operations and gaps are read from the first device: the cells so far
    # hold one, and on a mesh every device runs the same program.
    events0 = devices[sorted(devices)[0]]
    self_ns, calls = _self_times(events0)
    ranked = sorted(self_ns.items(), key=lambda kv: -kv[1])
    merged0 = _merge(events0)
    gaps = sorted(
        ((b_start - a_end, a_end) for (_, a_end), (b_start, _) in zip(merged0, merged0[1:])),
        reverse=True,
    )[:top]
    spans = loaded["spans"]

    def doing(at_ns: int) -> str:
        inside = [name for name, start, duration in spans if start <= at_ns < start + duration]
        return inside[-1] if inside else "outside_spans"

    span_s = {}
    for name, _, duration in spans:
        span_s[name] = span_s.get(name, 0.0) + duration / 1e9
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "op_s": {name: ns / 1e9 for name, ns in ranked},
        "op_calls": calls,
        "span_s": span_s,
        "top_ops": [[name, ns / 1e9] for name, ns in ranked[:top]],
        "idle_gaps": [[doing(start + gap // 2), gap / 1e9] for gap, start in gaps],
    }
