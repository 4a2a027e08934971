"""Profiler trace -> device busy time, idle share, kernel time by name, and
idle gaps attributed to what the host was doing.

The reduction works on a plain structure, so a recorded trace can be kept
as JSON and checked: ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``.  :func:`from_xplane` builds
it from the profiler's ``.xplane.pb``.

Device operations are the events of the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane.  Host spans are the benchmark's own
``TraceAnnotation`` events (``bench.*``) on the host plane; the measured
window is the ``bench.window`` span.
"""
from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"


def from_xplane(path: str, keep_host: tuple = ("bench.",)) -> dict:
    """The plain structure of one ``.xplane.pb``: every device plane's op
    line, and the host events whose names start with ``keep_host``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [{"name": ln.name,
                      "events": [[e.name, e.start_ns, e.duration_ns]
                                 for e in ln.events]}
                     for ln in plane.lines if ln.name == OPS_LINE]
        elif plane.name.startswith("/host:"):
            lines = [{"name": ln.name,
                      "events": [[e.name, e.start_ns, e.duration_ns]
                                 for e in ln.events
                                 if e.name.startswith(keep_host)]}
                     for ln in plane.lines]
            lines = [ln for ln in lines if ln["events"]]
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def device_ops(trace: dict) -> dict:
    """``{plane name: [(op name, start_ns, end_ns), ...]}``."""
    out = {}
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PREFIX):
            out[plane["name"]] = [(n, s, s + d) for ln in plane["lines"]
                                  if ln["name"] == OPS_LINE
                                  for n, s, d in ln["events"]]
    return out


def leaves(ops: list) -> list:
    """The ops that hold no other op: the line nests a loop's body ops
    inside the loop's own event, which would count their time twice."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, (n, s, e) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or not (nxt[1] < e and nxt[2] <= e):
            out.append((n, s, e))
    return out


def short(name: str) -> str:
    """An HLO op's text without layouts and attributes:
    ``%call.9 = f32[128,512] custom-call(bf16[128,64], bf16[64,512])``."""
    out, depth = [], 0
    for ch in name:
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        elif depth == 0:
            out.append(ch)
    text = "".join(out)
    for cut in (", custom_call_target", ", kind=", ", calls=", ", padding="):
        text = text.split(cut)[0]
    return re.sub(r" %[\w.\-]+", "", text)[:200]


def host_spans(trace: dict) -> list:
    """``[(span name, start_ns, end_ns), ...]`` of the host planes."""
    return [(n, s, s + d) for plane in trace["planes"]
            if plane["name"].startswith("/host:")
            for ln in plane["lines"] for n, s, d in ln["events"]]


def window(trace: dict) -> tuple[float, float]:
    """The measured window: the (longest) ``bench.window`` span."""
    spans = [(s, e) for n, s, e in host_spans(trace) if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    return max(spans, key=lambda se: se[1] - se[0])


def union(intervals, lo: float, hi: float) -> list:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(busy: list, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` around the merged ``busy`` ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: tuple, spans: list) -> str:
    """The host span that overlaps the gap most ("host" if none does)."""
    best, name = 0.0, "host"
    for n, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def reduce(trace: dict, extra_spans: list = ()) -> dict:
    """Busy and idle seconds over the window, averaged over the device
    planes; device time by op name; the longest idle gaps by label.
    ``extra_spans`` adds labelled host intervals (compiles) in trace ns."""
    lo, hi = window(trace)
    planes = device_ops(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    spans = [sp for sp in host_spans(trace) if sp[0] != WINDOW_SPAN]
    spans += list(extra_spans)
    busy_total, op_time, idle = 0.0, {}, []
    for ops in planes.values():
        merged = union([(s, e) for _, s, e in ops], lo, hi)
        busy_total += sum(e - s for s, e in merged)
        for n, s, e in leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[n] = op_time.get(n, 0.0) + d
        idle.extend(gaps(merged, lo, hi))
    n = len(planes)
    by_label: dict = {}
    longest = sorted(idle, key=lambda g: g[1] - g[0], reverse=True)
    for g in longest:
        by_label.setdefault(label(g, spans), []).append((g[1] - g[0]) / 1e9)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "op_s": {k: v / n / 1e9 for k, v in op_time.items()},
        "longest_gaps": [[label(g, spans), (g[1] - g[0]) / 1e9]
                         for g in longest[:10]],
        "gap_s_by_label": {k: sum(v) / n for k, v in by_label.items()},
        "window_ns": (lo, hi),
    }


def kernel_seconds(reduced: dict, match) -> float:
    """Device seconds of the ops for which ``match(name)`` holds."""
    return sum(v for k, v in reduced["op_s"].items() if match(k))


def breakdown(reduced: dict) -> dict:
    """The result line's ``breakdown``: the ten device ops that took most
    time (their times summed over ops of the same short name) and the ten
    longest idle gaps, each labelled."""
    by = {}
    for k, v in reduced["op_s"].items():
        by[short(k)] = by.get(short(k), 0.0) + v
    ops = sorted(by.items(), key=lambda kv: kv[1], reverse=True)
    return {"device_ops": [[k, v] for k, v in ops[:10]],
            "idle_gaps": reduced["longest_gaps"][:10]}


def trim(trace: dict, seconds: float) -> dict:
    """The first ``seconds`` of the window, events and window span cut to
    it: small enough to keep as a recorded trace."""
    lo, hi = window(trace)
    cut = min(hi, lo + seconds * 1e9)
    planes = []
    for plane in trace["planes"]:
        lines = []
        for ln in plane["lines"]:
            ev = []
            for n, s, d in ln["events"]:
                if n == WINDOW_SPAN and (s, s + d) == (lo, hi):
                    ev.append([n, lo, cut - lo])
                elif s < cut and s + d > lo:
                    ev.append([n, s, d])
            if ev:
                lines.append({"name": ln["name"], "events": ev})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}
