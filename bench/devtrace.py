"""From a profiler trace to numbers: device busy time, idle gaps, the
device time of named programs, and the ``breakdown`` of a traced run.

``read_xplane`` turns the profiler's ``.xplane.pb`` into a small plain
form (lists of ``[name, start_ns, dur_ns]``); everything else works on
that form, so the reduction can be checked on a recorded sample
(``bench/tests/data/trace_sample.json``).

* device planes are those named ``/device:TPU:<n>``; their ``XLA Ops``
  line holds the operations and ``XLA Modules`` the programs;
* host marks are the harness's own ``jax.profiler.TraceAnnotation``
  events (names starting ``bench.``) on the host plane, on the same clock.
"""

from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def read_xplane(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` in plain form."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = {"devices": [], "host_marks": [], "planes": []}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        out["planes"].append([plane.name, sorted(lines)])
        if DEVICE_PLANE.match(plane.name):
            dev = {"plane": plane.name, "ops": [], "modules": []}
            for key, line_name in (("ops", "XLA Ops"),
                                   ("modules", "XLA Modules")):
                if line_name in lines:
                    dev[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in lines[line_name].events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        out["host_marks"].append(
                            [e.name, int(e.start_ns), int(e.duration_ns)])
    return out


def _clip(events, lo: int, hi: int):
    """(starts, ends) int64 arrays of events clipped to [lo, hi]."""
    if not events:
        z = np.zeros(0, np.int64)
        return z, z
    a = np.asarray([(e[1], e[1] + e[2]) for e in events], np.int64)
    s = np.clip(a[:, 0], lo, hi)
    e = np.clip(a[:, 1], lo, hi)
    keep = e > s
    order = np.argsort(s[keep], kind="stable")
    return s[keep][order], e[keep][order]


def busy_and_gaps(events, lo: int, hi: int):
    """Seconds in which some event runs within [lo, hi] (the union of the
    intervals), and the idle gaps ``[(start_ns, end_ns)]`` between them,
    including any at the two ends."""
    s, e = _clip(events, lo, hi)
    if len(s) == 0:
        return 0.0, [(lo, hi)] if hi > lo else []
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    starts = s[new]
    ends = np.append(reach[np.flatnonzero(new)[1:] - 1], reach[-1])
    busy = int((ends - starts).sum())
    gaps = [(lo, int(starts[0]))] if starts[0] > lo else []
    gaps += [(int(a), int(b)) for a, b in zip(ends[:-1], starts[1:])]
    if ends[-1] < hi:
        gaps.append((int(ends[-1]), hi))
    return busy / 1e9, gaps


def module_seconds(dev: dict, pattern: str, lo: int, hi: int) -> float:
    """Device seconds of the programs whose name matches ``pattern``
    (a regular expression, searched from the start), within [lo, hi]."""
    rx = re.compile(pattern)
    s, e = _clip([m for m in dev["modules"] if rx.match(m[0])], lo, hi)
    return float((e - s).sum()) / 1e9


def op_kind(name: str) -> str:
    """``%merge_runs.29 = u32[...] custom-call(...)`` -> ``merge_runs``:
    the operation's name without its HLO text and its number."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def top_ops(dev: dict, lo: int, hi: int, n: int = 10):
    """The ``n`` kinds of device operation that took most time, each named
    ``<program>/<operation>``, with their summed seconds."""
    mods = sorted(dev["modules"], key=lambda m: m[1])
    m_start = np.asarray([m[1] for m in mods], np.int64)
    tot: dict[str, int] = collections.defaultdict(int)
    for name, t0, dur in dev["ops"]:
        a, b = max(t0, lo), min(t0 + dur, hi)
        if b <= a:
            continue
        i = int(np.searchsorted(m_start, t0, side="right")) - 1
        prog = mods[i][0].split("(")[0] \
            if i >= 0 and t0 < mods[i][1] + mods[i][2] else "?"
        tot[f"{prog}/{op_kind(name)}"] += b - a
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def attribute_gaps(gaps, spans, n: int = 10):
    """Idle seconds by the innermost host span open at each gap's middle.
    ``spans``: ``[(name, start_ns, end_ns)]`` on the trace's clock.  Gaps
    in which no span is open count as ``client`` (the benchmark's own
    loop, between calls into the store)."""
    mids = sorted(((a + b) // 2, b - a) for a, b in gaps)
    spans = sorted(spans, key=lambda s: s[1])
    tot: dict[str, int] = collections.defaultdict(int)
    active: list = []
    j = 0
    for mid, length in mids:
        while j < len(spans) and spans[j][1] <= mid:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[2] > mid]
        name = min(active, key=lambda s: s[2] - s[1])[0] if active \
            else "client"
        tot[name] += length
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def window_of(tr: dict, mark: str = "bench.window"):
    """[start_ns, end_ns] of the harness's window mark."""
    for name, t0, dur in tr["host_marks"]:
        if name == mark:
            return t0, t0 + dur
    raise KeyError(f"no {mark!r} mark in the trace")


def reduce(tr: dict, spans=(), mark: str = "bench.window") -> dict:
    """Busy seconds (averaged over the chips), window seconds and the
    ``breakdown`` of the traced window."""
    lo, hi = window_of(tr, mark)
    if not tr["devices"]:
        raise ValueError("the trace has no TPU device plane; planes: "
                         f"{tr.get('planes')}")
    busys, gaps0 = [], None
    for dev in tr["devices"]:
        busy, gaps = busy_and_gaps(dev["ops"] or dev["modules"], lo, hi)
        busys.append(busy)
        gaps0 = gaps if gaps0 is None else gaps0
    dev0 = tr["devices"][0]
    return {"busy_s": sum(busys) / len(busys), "window_s": (hi - lo) / 1e9,
            "lo": lo, "hi": hi,
            "breakdown": {"device_ops": top_ops(dev0, lo, hi),
                          "idle_gaps": attribute_gaps(gaps0, spans)}}
