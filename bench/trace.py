"""From a profiler trace to busy time, per-call device time and a
breakdown, on the trace's own clock.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a plain
dict: the device operations of every TPU (start and end in ns, name,
chip), the benchmark's own host spans (``marks``: the ``bench.*``
annotations ``window``, ``step``, and the backend calls ``prefill_wave``,
``decode``, ``splice``, ``extract``), the program's own spans
(``program_marks``: the ``repro.*`` annotations of the span log the
harness attaches in traced runs) and each program the TPUs ran
(``modules``: the jitted call's name, e.g. ``jit_paged_decode``).  All sit
on the trace's clock, so a span's device time is the part of the
operations' union that falls inside it.  Everything after ``load`` works
on that dict, which is what the tests check on a trace recorded on the
chip.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir) -> dict:
    """``ops`` (start, end, name, chip), ``marks`` and ``program_marks``
    (name without its prefix, start, end) and ``modules`` (name without
    its ``(id)``, start, end, chip), in ns, from the newest profile under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        return {"ops": [], "marks": [], "devices": 0, "program_marks": [],
                "modules": []}
    pd = ProfileData.from_file(str(paths[-1]))
    ops, marks, devices = [], [], set()
    program_marks, modules = [], []
    for plane in pd.planes:
        m = DEVICE.match(plane.name)
        if m:
            devices.add(int(m.group(1)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, int(m.group(1))) for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((e.name.split("(")[0], e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    int(m.group(1))) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        marks.append((e.name[6:], e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name.startswith("repro."):
                        program_marks.append((e.name[6:], e.start_ns,
                                              e.start_ns + e.duration_ns))
    ops.sort()
    marks.sort(key=lambda x: x[1])
    program_marks.sort(key=lambda x: (x[1], -x[2]))
    modules.sort(key=lambda x: x[1])
    return {"ops": ops, "marks": marks, "devices": len(devices),
            "program_marks": program_marks, "modules": modules}


def window(td: dict):
    """(start, end) of the traced window, in ns, or None."""
    w = [m for m in td["marks"] if m[0] == "window"]
    return (w[0][1], w[0][2]) if w else None


def union(intervals) -> list[tuple]:
    out: list[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def clipped(intervals, lo, hi) -> list[tuple]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def busy_ns(td: dict, lo, hi, device=None) -> float:
    """Union of device-busy time inside [lo, hi), averaged over chips."""
    if not td["devices"]:
        return 0.0
    devs = sorted({o[3] for o in td["ops"]}) if device is None else [device]
    total = 0
    for d in devs:
        u = union(clipped([(a, b) for a, b, _, dd in td["ops"] if dd == d],
                          lo, hi))
        total += sum(b - a for a, b in u)
    return total / max(td["devices"], 1)


def busy(td: dict) -> dict:
    """``busy_s`` and ``window_s`` of the traced window."""
    w = window(td)
    if w is None:
        return {}
    return {"busy_s": busy_ns(td, *w) / 1e9, "window_s": (w[1] - w[0]) / 1e9}


def per_call(td: dict, name: str) -> list[float]:
    """Device-busy seconds inside each ``name`` span of the window."""
    w = window(td)
    if w is None or not td["ops"]:
        return []
    return [busy_ns(td, a, b) / 1e9 for n, a, b in td["marks"]
            if n == name and a >= w[0] and b <= w[1]]


def _host_at(td: dict, t) -> str:
    """The innermost benchmark span around time ``t`` (the host's
    activity then), or ``driver`` outside every span."""
    best = None
    for n, a, b in td["marks"]:
        if n == "window" or not (a <= t < b):
            continue
        if best is None or b - a < best[1] - best[0]:
            best = (a, b, n)
    return best[2] if best else "driver"


def breakdown(td: dict, top: int = 10) -> dict:
    """The device operations that took most time in the window, and the
    longest idle gaps with what the host was doing in each."""
    w = window(td)
    if w is None:
        return {"device_ops": [], "idle_gaps": []}
    per: dict = {}
    for a, b, n, _ in td["ops"]:
        if b > w[0] and a < w[1]:
            per[n] = per.get(n, 0) + (min(b, w[1]) - max(a, w[0]))
    ops = sorted(per.items(), key=lambda x: -x[1])[:top]
    u = union(clipped([(a, b) for a, b, _, _ in td["ops"]], *w))
    edges = [w[0]] + [x for ab in u for x in ab] + [w[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_host_at(td, (a + b) // 2), (b - a) / 1e9]
            for a, b in gaps[:top]]
    return {"device_ops": [[n, s / 1e9] for n, s in ops], "idle_gaps": idle}
