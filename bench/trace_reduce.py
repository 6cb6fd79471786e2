"""Reduce a profiler trace (``.xplane.pb``) to the numbers a run reports.

The traced window is the host span ``bench.window`` that the harness opens
around the measured requests.  Within it, per device plane
(``/device:TPU:<n>``):

- busy time: the union of the intervals of the device's operations (the
  ``XLA Ops`` line); the idle share is 1 minus busy over the window;
- operation time: the summed duration of each operation name, and its
  number of events (``ops``);
- idle gaps: each stretch of the window in which the device ran nothing,
  attributed to what the host was doing throughout it (the shortest host
  span, on any thread, that covers the whole gap) and summed per span
  name; gaps under 50 us are summed apart.

Busy time and the window are averaged over the devices that ran anything
in the window.  Only ``jax.profiler.ProfileData`` is used to read the file.
"""
from __future__ import annotations

import glob
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
NO_SPAN = "(no host span)"
SMALL_GAP_NS = 50_000
SMALL_GAPS = "(gaps under 50 us)"
WAITING_THREADS = ("futex", "EventFD")  # threads that only wait


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def gaps(busy, w0, w1) -> list:
    """The parts of ``[w0, w1]`` that no interval of ``busy`` covers."""
    out, t = [], w0
    for s, e in busy:
        if s > t:
            out.append((t, min(s, w1)))
        t = max(t, e)
        if t >= w1:
            break
    if t < w1:
        out.append((t, w1))
    return [(s, e) for s, e in out if e > s]


def attribute(threads, gap_list) -> list:
    """For each gap, the name of the shortest host span that covers all of
    it, on any of ``threads`` (each a list of ``(start, end, name)``
    spans, nested as a thread's spans are); ``NO_SPAN`` where none does."""
    best = [None] * len(gap_list)
    order = sorted(range(len(gap_list)), key=lambda i: gap_list[i])
    for spans in threads:
        spans = sorted(spans)
        stack, j = [], 0
        for gi in order:
            s, e = gap_list[gi]
            while j < len(spans) and spans[j][0] <= s:
                while stack and stack[-1][1] <= spans[j][0]:
                    stack.pop()
                stack.append(spans[j])
                j += 1
            for sp in reversed(stack):  # innermost first
                if sp[1] >= e:
                    if best[gi] is None or sp[1] - sp[0] < best[gi][1]:
                        best[gi] = (sp[2], sp[1] - sp[0])
                    break
    return [b[0] if b else NO_SPAN for b in best]


def reduce_planes(planes) -> dict:
    """The reduction over already-read planes: ``planes`` is a list of
    ``(plane name, [(line name, [(event name, start_ns, dur_ns)])])``."""
    window, threads = None, []
    for pname, lines in planes:
        if not pname.startswith(HOST_PLANE):
            continue
        for lname, events in lines:
            spans = []
            for name, s, d in events:
                if name == WINDOW_SPAN:
                    window = (s, s + d)
                else:
                    spans.append((s, s + d, name))
            if not lname.startswith(WAITING_THREADS):
                threads.append(spans)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    w0, w1 = window
    devices = {}
    for pname, lines in planes:
        if not pname.startswith(DEVICE_PREFIX):
            continue
        ops, intervals = {}, []
        for lname, events in lines:
            if lname != OPS_LINE:
                continue
            for name, s, d in events:
                e = s + d
                if e <= w0 or s >= w1:
                    continue
                s, e = max(s, w0), min(e, w1)
                intervals.append((s, e))
                n, t = ops.get(name, (0, 0))
                ops[name] = (n + 1, t + (e - s))
        if not intervals:
            continue
        busy = union(intervals)
        idle_list = gaps(busy, w0, w1)
        long = [g for g in idle_list if g[1] - g[0] >= SMALL_GAP_NS]
        idle = {SMALL_GAPS: sum(e - s for s, e in idle_list
                                if e - s < SMALL_GAP_NS)}
        for (s, e), name in zip(long, attribute(threads, long)):
            idle[name] = idle.get(name, 0) + (e - s)
        devices[pname] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "ops": {k: (n, t * 1e-9) for k, (n, t) in ops.items()},
            "idle": {k: t * 1e-9 for k, t in idle.items() if t},
        }
    window_s = (w1 - w0) * 1e-9
    n = max(len(devices), 1)
    ops, idle = {}, {}
    for dev in devices.values():
        for k, (c, t) in dev["ops"].items():
            c0, t0 = ops.get(k, (0, 0.0))
            ops[k] = (c0 + c, t0 + t)
        for k, t in dev["idle"].items():
            idle[k] = idle.get(k, 0.0) + t / n
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / n,
        "devices": sorted(devices),
        "ops": ops,
        "idle_gaps": idle,
    }


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, int(e.start_ns), int(e.duration_ns))
                                 for e in ln.events]) for ln in p.lines])
            for p in data.planes]


def reduce(path: str) -> dict:
    return reduce_planes(read_planes(path))


def idle_pct(red) -> float | None:
    """The idle share of the window, in %; none without device activity."""
    if not red or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def top(d: dict, n: int = 10) -> list:
    """The ``n`` largest entries of ``d`` as ``[name, value]`` pairs."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(red: dict, width: int = 160) -> dict:
    """The device operations that took most time (HLO text cut to
    ``width`` characters, where the operands trail off) and the longest
    idle gaps by host span."""
    ops = {}
    for k, (_, t) in red["ops"].items():
        ops[k[:width]] = ops.get(k[:width], 0.0) + t
    return {"device_ops": top(ops), "idle_gaps": top(red["idle_gaps"])}
