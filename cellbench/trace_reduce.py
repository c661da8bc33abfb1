"""From a ``jax.profiler`` trace of the window to device numbers.

The profiler's ``.xplane.pb`` is first flattened to a plain form (``planes`` ->
``lines`` -> ``events`` as ``[name, start_ns, duration_ns]``), which is also
how the recorded trace beside the tests is kept; every reduction works on that
form, so the tests check exactly the code a run uses.

* busy: the union of the intervals in which an operation ran on a device
  (line ``XLA Ops``), cut to the timed calls, averaged over the devices;
* window: the summed length of the timed calls (the harness marks each with a
  ``cellbench.call`` annotation on the host).  The untimed gaps in which the
  harness publishes the next batch are left out on both sides;
* kernel time: summed durations of the device events on a named line whose
  name contains one of the given strings;
* idle gaps: the parts of the timed calls in which no operation ran on the
  first device, each instant charged to the innermost host span open in it
  (the program's spans reach the trace through ``trace.jax_annotations``).

``python -m cellbench.trace_reduce <dir-or-file>`` prints the planes, lines
and the longest events of a trace: look at one by hand before writing a
``match`` against it.
"""

from __future__ import annotations

import glob
import heapq
import json
import os
import re
import sys

import numpy as np

CALL = "cellbench.call"
DEVICE_PLANE = "/device:"
HOST_PLANE = "/host:"
OPS_LINE = "XLA Ops"
NO_SPAN = "(no span open)"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An event's name without what varies from compile to compile: an XLA op
    is named by its whole HLO text (``%fusion.3 = s32[...] fusion(...)``) and
    a module by ``jit_f(<fingerprint>)``; keep ``fusion.3`` and ``jit_f``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def load_xplane(path: str) -> dict:
    """An ``.xplane.pb`` in the plain form."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return {"planes": [
        {"name": plane.name, "lines": [
            {"name": line.name, "events": [
                [short_name(e.name), float(e.start_ns), float(e.duration_ns)]
                for e in line.events
            ]}
            for line in plane.lines
        ]}
        for plane in data.planes
    ]}


def _intervals(events) -> np.ndarray:
    """``(n, 2)`` start/end in ns of events that last."""
    iv = np.array([[s, s + d] for _, s, d in events if d > 0], dtype=np.float64)
    return iv.reshape(-1, 2)


def union(iv: np.ndarray) -> np.ndarray:
    """Sorted, disjoint intervals covering the same time as ``iv``."""
    if len(iv) == 0:
        return np.empty((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    first = np.r_[True, iv[1:, 0] > ends[:-1]]
    starts = iv[first, 0]
    last = np.r_[first[1:], True]
    return np.stack([starts, ends[last]], axis=1)


def clip(iv: np.ndarray, windows: np.ndarray) -> np.ndarray:
    """The parts of the disjoint intervals ``iv`` inside the disjoint
    ``windows``."""
    out = []
    for w0, w1 in windows:
        part = iv[(iv[:, 1] > w0) & (iv[:, 0] < w1)]
        if len(part):
            out.append(np.stack(
                [np.maximum(part[:, 0], w0), np.minimum(part[:, 1], w1)], axis=1
            ))
    return np.concatenate(out) if out else np.empty((0, 2))


def complement(iv: np.ndarray, windows: np.ndarray) -> np.ndarray:
    """The parts of ``windows`` that the disjoint, sorted ``iv`` leave free."""
    out = []
    for w0, w1 in windows:
        part = iv[(iv[:, 1] > w0) & (iv[:, 0] < w1)]
        edges = np.r_[w0, np.clip(part.reshape(-1), w0, w1), w1].reshape(-1, 2)
        out.append(edges[edges[:, 1] > edges[:, 0]])
    return np.concatenate(out) if out else np.empty((0, 2))


def _seconds(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) / 1e9


def _lines(trace: dict, plane_prefix: str, line_name: str | None = None):
    for plane in trace["planes"]:
        if plane["name"].startswith(plane_prefix):
            for line in plane["lines"]:
                if line_name is None or line["name"] == line_name:
                    yield plane["name"], line


def call_windows(trace: dict) -> np.ndarray:
    """The timed calls, as the harness annotated them on the host."""
    calls = [
        e for _, line in _lines(trace, HOST_PLANE) for e in line["events"]
        if e[0] == CALL
    ]
    return union(_intervals(calls))


def kernel_seconds(trace: dict, line_name: str, match: list) -> tuple:
    """``(seconds, events)`` of the device events on ``line_name`` whose name
    contains one of ``match``, averaged over the devices."""
    total, count, planes = 0.0, 0, set()
    for plane, line in _lines(trace, DEVICE_PLANE, line_name):
        planes.add(plane)
        for name, _, dur in line["events"]:
            if any(m in name for m in match):
                total += dur
                count += 1
    n = max(1, len(planes))
    return total / 1e9 / n, count // n


def charge_gaps(gaps: np.ndarray, spans: list) -> dict:
    """Seconds of idle time by the host span open in them.  One sweep over
    the time line: every instant of a gap goes to the shortest span open at
    that instant (the innermost, where spans nest), and to ``NO_SPAN`` where
    none is."""
    points = []
    for i, (_, start, dur) in enumerate(spans):
        if dur > 0:
            points.append((start, 1, i))
            points.append((start + dur, 0, i))
    for g0, g1 in np.asarray(gaps).tolist():
        points.append((g0, 3, -1))
        points.append((g1, 2, -1))
    points.sort()
    out: dict = {}
    heap, open_now, in_gap, prev = [], set(), False, 0.0
    for t, what, i in points:
        if in_gap and t > prev:
            while heap and heap[0][1] not in open_now:
                heapq.heappop(heap)
            name = spans[heap[0][1]][0] if heap else NO_SPAN
            out[name] = out.get(name, 0.0) + (t - prev) / 1e9
        if what == 1:
            open_now.add(i)
            heapq.heappush(heap, (spans[i][2], i))
        elif what == 0:
            open_now.discard(i)
        else:
            in_gap = what == 3
        prev = t
    return out


def reduce(trace: dict) -> dict:
    """``window_s``, ``busy_s``, ``devices``, and the two lists of the
    result line's ``breakdown``."""
    windows = call_windows(trace)
    busy, per_op, first_busy = [], {}, None
    for _, line in _lines(trace, DEVICE_PLANE, OPS_LINE):
        iv = clip(union(_intervals(line["events"])), windows)
        busy.append(_seconds(iv))
        if first_busy is None:
            first_busy = iv
        for name, _, dur in line["events"]:
            per_op[name] = per_op.get(name, 0.0) + dur / 1e9
    n = max(1, len(busy))
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    spans = [
        e for _, line in _lines(trace, HOST_PLANE) for e in line["events"]
        if e[0] != CALL
    ]
    gaps = complement(
        first_busy if first_busy is not None else np.empty((0, 2)), windows
    )
    idle = sorted(charge_gaps(gaps, spans).items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": _seconds(windows),
        "busy_s": sum(busy) / n,
        "devices": len(busy),
        "device_ops": [[k, v / n] for k, v in device_ops],
        "idle_gaps": [[k, v] for k, v in idle],
    }


def sample(trace: dict, calls: int = 2, cap: int = 400) -> dict:
    """The trace cut to its first ``calls`` timed calls, at most ``cap``
    events to a line: small enough to keep beside the tests."""
    windows = call_windows(trace)[:calls]
    if len(windows) == 0:
        return {"planes": []}
    t0, t1 = windows[0][0], windows[-1][1]
    planes = []
    for plane in trace["planes"]:
        lines = [
            {"name": line["name"], "events": [
                e for e in line["events"] if e[1] >= t0 and e[1] + e[2] <= t1
            ][:cap]}
            for line in plane["lines"]
        ]
        lines = [ln for ln in lines if ln["events"]]
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def describe(trace: dict, top: int = 12) -> str:
    """Planes, lines and each line's longest event names, for a reader."""
    out = []
    for plane in trace["planes"]:
        out.append(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            agg: dict = {}
            for name, _, dur in line["events"]:
                c, s = agg.get(name, (0, 0.0))
                agg[name] = (c + 1, s + dur)
            out.append(f"  LINE {line['name']}: {len(line['events'])} events")
            for name, (c, s) in sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {s / 1e6:12.3f} ms  x{c:<6} {name[:120]}")
    return "\n".join(out)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.strip().splitlines()[-3], file=sys.stderr)
        return 2
    path = argv[0]
    if path.endswith(".json"):
        with open(path) as fh:
            trace = json.load(fh)
    else:
        trace = load_xplane(path if path.endswith(".pb") else find_xplane(path))
    print(describe(trace))
    print(json.dumps(reduce(trace)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
