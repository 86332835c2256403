"""Reduce a ``jax.profiler`` trace to the numbers the metrics read.

The trace is the ``.xplane.pb`` the profiler writes.  Its device planes
(``/device:TPU:<i>``) hold a line of program executions (``XLA Modules``,
events named ``<module>(<fingerprint>)``).  The host plane (``/host:CPU``) holds a
line per host thread, among them the bench's own ``TraceAnnotation``s and
the main thread's dispatches (``main/<tid>``, or ``python`` where the
Python tracer is on).
Every event carries a start and a duration in nanoseconds, but a device
plane's clock is offset from the host's (on the v5e by some 2 ms, the
device's programs appear to start before the host enqueued them).  Each
program is linked to the host event that enqueued it by a flow id (the
device event's ``_c`` stat is the host event's ``_p``), and ``summarize``
shifts each device's programs onto the host clock by the least gap between
a program's start and its enqueue, so that a program lies inside the host
annotation that enqueued it and waited for it.

``Summary`` keeps the intervals per device and answers: device busy time
(the union of program executions), the launch count, device time per
program family, the idle gaps and what the host was doing in each.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

_FAMILY = re.compile(r"^(.*)\(\d+\)$")


def family(module_event_name: str) -> str:
    """``jit_norm(5961132016729099988)`` -> ``jit_norm``."""
    m = _FAMILY.match(module_event_name)
    return m.group(1) if m else module_event_name


def union(intervals) -> list:
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    b = union(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class Device:
    name: str
    modules: list = field(default_factory=list)   # (family, start_ns, end_ns)
    offset_ns: float | None = None   # subtracted to put it on the host clock


@dataclass
class Summary:
    devices: list                                  # [Device], by device index
    host: list            # (name, start_ns, end_ns, depth, on the main thread)

    # -- the window -----------------------------------------------------

    def annotation_window(self, name: str) -> tuple | None:
        """From the first start to the last end of the host annotations
        called ``name``; None when there are none."""
        spans = [(s, e) for n, s, e, *_ in self.host if n == name]
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)

    # -- per device -----------------------------------------------------

    def busy_ns(self, dev: Device, t0: float, t1: float) -> float:
        return total(clip(union((s, e) for _, s, e in dev.modules), t0, t1))

    def launches(self, dev: Device, t0: float, t1: float) -> int:
        return sum(1 for _, s, _e in dev.modules if t0 <= s < t1)

    def families(self, dev: Device, t0: float, t1: float) -> dict:
        """family -> [count, device ns] of the programs started in the window."""
        out: dict = {}
        for fam, s, e in dev.modules:
            if t0 <= s < t1:
                c = out.setdefault(fam, [0, 0.0])
                c[0] += 1
                c[1] += e - s
        return out

    def idle_gaps(self, dev: Device, t0: float, t1: float) -> list:
        """(start, end) of the device's idle intervals in the window."""
        busy = clip(union((s, e) for _, s, e in dev.modules), t0, t1)
        return subtract([(t0, t1)], busy)

    def host_activity(self, t: float) -> str:
        """The innermost event of the main thread that covers time ``t``."""
        best, depth = "idle", -1
        for name, s, e, d, main in self.host:
            if main and s <= t < e and d > depth:
                best, depth = name, d
        return best


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def _flow(event, key: str):
    """The event's flow id under ``key`` (``_p`` producer, ``_c``
    consumer), or None."""
    for name, value in event.stats:
        if name == key:
            return value
    return None


def clock_offset(programs, enqueued: dict) -> float | None:
    """The least ``start - enqueue`` over the (start_ns, flow id) of a
    device's programs whose flow id ``enqueued`` maps to the host start of
    the event that enqueued them; None where no program is linked.  A
    program starts after its enqueue, so subtracting this puts each one at
    or after its enqueue on the host clock."""
    gaps = [s - enqueued[f] for s, f in programs if f is not None and f in enqueued]
    return min(gaps) if gaps else None


def _nest_depths(events, main: bool) -> list:
    """Depth of each (name, start, end) event in its line's nesting."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1] <= s:
            stack.pop()
        out.append((name, s, e, len(stack), main))
        stack.append(e)
    return out


def _is_main_thread(line_name: str) -> bool:
    """The host line of Python's main thread: ``main/<tid>`` on the TPU
    host, ``python`` where the Python tracer is on or on the CPU backend."""
    return line_name == "python" or line_name.startswith("main/")


def summarize(profile) -> Summary:
    """Build a ``Summary`` from a ``jax.profiler.ProfileData``."""
    devices, host, enqueued, flows = [], [], {}, {}
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [(family(n), s, e) for n, s, e in _events(line)]
                    flows[plane.name] = [(m[1], _flow(e, "_c"))
                                         for m, e in zip(dev.modules, line.events)]
            if dev.modules:
                devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(_nest_depths(list(_events(line)), _is_main_thread(line.name)))
                for e in line.events:
                    f = _flow(e, "_p")
                    if f is not None:
                        enqueued.setdefault(f, float(e.start_ns))
    for dev in devices:
        off = dev.offset_ns = clock_offset(flows[dev.name], enqueued)
        if off:
            dev.modules = [(f, s - off, e - off) for f, s, e in dev.modules]
    devices.sort(key=lambda d: int(re.sub(r"\D", "", d.name) or 0))
    return Summary(devices, host)


def load(path: str) -> Summary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(path))
