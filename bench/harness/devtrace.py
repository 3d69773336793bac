"""Reduction of a profiler trace to device time, idle gaps and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events; ``reduce`` works on those alone, so a small recorded trace checks
it without a chip.  The window is the host span ``bench.window``; device
time outside it is ignored.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

from .spans import SPAN_PREFIX

WINDOW = SPAN_PREFIX + "window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host runtime events that carry the ``run_id`` of the program they enqueue
# or complete: they tie the device's clock to the host's
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()  # (key, value) pairs

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def short(self) -> str:
        """The HLO instruction's name: a TPU trace names each op by the
        instruction's whole text, ``%int8_matmul.10 = f32[...] custom-call(...)``."""
        return self.name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load(path: str) -> list[Event]:
    """Of one trace: the device's ops and programs, the host's enqueue and
    completion of each program, and the benchmark's host spans."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    stats = ()
                elif e.name in (ENQUEUE, COMPLETE):
                    stats = tuple((k, v) for k, v in e.stats if k == "run_id")
                else:
                    continue
                if device and line.name == MODULES_LINE:
                    stats = tuple((k, v) for k, v in e.stats if k == "run_id")
                out.append(Event(plane.name, line.name, e.name, float(e.start_ns),
                                 float(e.duration_ns), stats))
    return out


def clock_offset_ns(events: list[Event]) -> float:
    """What to add to device times to put them on the host's clock.

    Each program runs on the device after the host enqueued it and before
    the host saw it complete; per ``run_id`` the midpoint of the offsets
    that bound it, and the median over programs.  0 when the trace holds no
    such pairs."""
    enq, done, mods = {}, {}, {}
    for e in events:
        rid = dict(e.stats).get("run_id")
        if rid is None:
            continue
        if e.name == ENQUEUE:
            enq[str(rid)] = e.end_ns
        elif e.name == COMPLETE:
            done[str(rid)] = e.start_ns
        elif e.line == MODULES_LINE:
            mods[str(rid)] = e
    mids = sorted(((enq[r] - m.start_ns) + (done[r] - m.end_ns)) / 2
                  for r, m in mods.items() if r in enq and r in done)
    return mids[len(mids) // 2] if mids else 0.0


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # union of device op intervals, averaged over the devices used
    devices: int
    op_s: dict  # instruction name without its number -> device self seconds, all devices
    gap_s: dict  # host span name (or "host_other") -> idle device seconds
    ops: list  # the device op events inside the window
    clock_offset_s: float = 0.0  # added to device times to put them on the host's clock

    def seconds_matching(self, pattern: str) -> float:
        """Device seconds of the ops whose instruction name matches."""
        rx = re.compile(pattern)
        return sum(e.dur_ns for e in self.ops if rx.search(e.short)) / 1e9

    def count_matching(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(1 for e in self.ops if rx.search(e.short))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(events: list[Event]) -> Reduction:
    windows = [e for e in events if e.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span in the trace, found {len(windows)}")
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    host = [e for e in events if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW]
    shift = clock_offset_ns(events)
    per_plane = defaultdict(list)
    ops = []
    for e in events:
        if not DEVICE_PLANE.match(e.plane) or e.line != OPS_LINE:
            continue
        e = dataclasses.replace(e, start_ns=e.start_ns + shift)
        a, b = max(e.start_ns, w0), min(e.end_ns, w1)
        if b <= a:
            continue
        per_plane[e.plane].append((a, b))
        ops.append(e)
    op_s = _self_seconds(ops, w0, w1)
    if not per_plane:
        raise ValueError("no device operation ran inside the window")
    busy = {p: _union(iv) for p, iv in per_plane.items()}
    busy_s = sum(sum(b - a for a, b in m) for m in busy.values()) / len(busy) / 1e9
    labels = _label_segments(host, w0, w1)
    gap_s: dict[str, float] = defaultdict(float)
    for merged in busy.values():
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, secs in _intersect(gaps, labels):
            gap_s[name] += secs / len(busy)
    return Reduction(window_s=(w1 - w0) / 1e9, busy_s=busy_s, devices=len(busy),
                     op_s=dict(op_s), gap_s=dict(gap_s), ops=ops, clock_offset_s=shift / 1e9)


def _self_seconds(ops: list[Event], w0: float, w1: float) -> dict:
    """Device seconds inside [w0, w1) of each instruction name (its number
    dropped), less the time of the ops nested in it: a loop's own time
    without its body's."""
    out: dict[str, float] = defaultdict(float)
    by_line = defaultdict(list)
    for e in ops:
        by_line[(e.plane, e.line)].append(e)
    for evs in by_line.values():
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        stack: list = []  # [event, self ns]
        for e in evs:
            while stack and stack[-1][0].end_ns <= e.start_ns:
                done = stack.pop()
                out[_family(done[0])] += done[1] / 1e9
            own = min(e.end_ns, w1) - max(e.start_ns, w0)
            if stack:
                stack[-1][1] -= min(e.end_ns, stack[-1][0].end_ns, w1) - max(e.start_ns, w0)
            stack.append([e, own])
        for e, ns in stack:
            out[_family(e)] += ns / 1e9
    return dict(out)


def _family(e: Event) -> str:
    return re.sub(r"\.\d+$", "", e.short)


def _label_segments(host: list[Event], w0: float, w1: float):
    """The window cut into ``(start, end, name)`` segments by what the host
    was doing: the innermost benchmark span open at each instant, else
    ``host_other``."""
    marks = sorted([(h.start_ns, 1, i) for i, h in enumerate(host)] +
                   [(h.end_ns, 0, i) for i, h in enumerate(host)])
    segs, stack, t = [], [], w0
    for x, opening, i in marks:
        x = min(max(x, w0), w1)
        if x > t:
            name = host[stack[-1]].name[len(SPAN_PREFIX):] if stack else "host_other"
            segs.append((t, x, name))
            t = x
        if opening:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    if w1 > t:
        segs.append((t, w1, host[stack[-1]].name[len(SPAN_PREFIX):] if stack else "host_other"))
    return segs


def _intersect(gaps, segs):
    """Seconds of each segment name inside the gaps; both lists sorted."""
    out: dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s0, s1, name = segs[k]
            out[name] += (min(b, s1) - max(a, s0)) / 1e9
            k += 1
    return out.items()
