"""What the program's own trace records say about a traced window.

    python3 bench/engine_trace.py <file.xplane.pb | trace.json>

prints :func:`engine`'s reading of one trace as JSON.  ``ServeEngine``
records ``engine.*`` host spans (``jax.profiler.TraceAnnotation``), its
jitted steps show as ``jit_engine_*`` events on each device's
``XLA Modules`` line, and the tied head's ops carry ``head`` in their scope
path (``jax.named_scope``).  :func:`from_xplane` keeps those beside what
``bench/trace_reduce.py`` keeps (the ``bench.*`` spans and the
``XLA Ops`` line), in the same plain structure, with each op's scope path
as the ``scopes`` list beside the op line's events.  :func:`engine` splits
the device's idle time by the innermost open engine span and times decode
and the head on the device.  ``trace_reduce.reduce`` reads none of this.
"""
from __future__ import annotations

import bisect
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

MODULES_LINE = "XLA Modules"
BENCH_PREFIX = "bench."
ENGINE_PREFIX = "engine."
#: the op-event stat that holds the op's scope path (``jax.named_scope``)
SCOPE_STAT = "tf_op"
#: the device modules of the engine's decode steps (``engine_decode*``)
DECODE_MODULE = "engine_decode"
HEAD_SCOPE = "head"
#: what the device's idle time is charged to, by the innermost open
#: engine span; every other ``engine.*`` span is the engine's own host
#: work ("host"), and no open engine span is the harness's
IDLE_KIND = {"engine.launch": "launch", "engine.sync": "sync"}


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: varints as ints,
    length-delimited fields as memoryviews, fixed-width fields as None."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _unjitted(scope: str) -> str:
    """The scope path without its leading ``jit(<module>)`` component."""
    head, _, rest = scope.partition("/")
    return rest if head.startswith("jit(") else scope


def op_scopes(path: str) -> dict:
    """``{device plane name: {op name: scope path or None}}`` of one
    ``.xplane.pb``.  A device op's scope path (``jax.named_scope``) is the
    ``tf_op`` stat of the op's event metadata, which ``ProfileData`` does
    not show, so the planes' metadata records are read here from the
    protobuf wire format (tsl ``xplane.proto``: XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5; map entries
    key = 1, value = 2; XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5).  The
    event lines are skipped.  One op name can name ops of several
    programs; where its records give different paths (past their
    ``jit(<module>)``), which one an event is cannot be told, and the
    name maps to None."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for num, plane in _fields(data):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.extend(v2 for k, v2 in _fields(v) if k == 2)
            elif pf == 5:
                for k, v2 in _fields(v):
                    if k == 2:
                        md = dict(_fields(v2))
                        stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        if not name.startswith(tr.DEVICE_PREFIX):
            continue
        seen = {}
        for ev in events:
            op, scope = "", ""
            for ef, v in _fields(ev):
                if ef == 2:
                    op = bytes(v).decode()
                elif ef == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == SCOPE_STAT and 5 in st:
                        scope = bytes(st[5]).decode()
            seen.setdefault(op, set()).add(scope)
        out[name] = {op: _pick(scopes) for op, scopes in seen.items()
                     if scopes != {""}}
    return out


def _pick(scopes: set):
    """The one scope path of an op name's records, None if they differ."""
    return min(scopes) if len({_unjitted(s) for s in scopes}) == 1 else None


def from_xplane(path: str) -> dict:
    """The plain structure of one ``.xplane.pb``: every device plane's op
    line (with each op's scope path, "" where it has none) and module line,
    and the ``bench.*`` and ``engine.*`` host events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    scopes = op_scopes(path)
    planes = []
    for plane in pd.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            lines = []
            for ln in plane.lines:
                if ln.name not in (tr.OPS_LINE, MODULES_LINE):
                    continue
                line = {"name": ln.name,
                        "events": [[e.name, e.start_ns, e.duration_ns]
                                   for e in ln.events]}
                if ln.name == tr.OPS_LINE:
                    sc = scopes.get(plane.name, {})
                    line["scopes"] = [sc.get(n, "") for n, _, _ in
                                      line["events"]]
                lines.append(line)
        elif plane.name.startswith("/host:"):
            lines = [{"name": ln.name,
                      "events": [[e.name, e.start_ns, e.duration_ns]
                                 for e in ln.events if e.name.startswith(
                                     (BENCH_PREFIX, ENGINE_PREFIX))]}
                     for ln in plane.lines]
            lines = [ln for ln in lines if ln["events"]]
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def modules(trace: dict) -> dict:
    """``{plane name: [(module name, start_ns, end_ns), ...]}``."""
    return {plane["name"]: [(n, s, s + d) for ln in plane["lines"]
                            if ln["name"] == MODULES_LINE
                            for n, s, d in ln["events"]]
            for plane in trace["planes"]
            if plane["name"].startswith(tr.DEVICE_PREFIX)}


def scoped_ops(trace: dict) -> dict:
    """``{plane name: {(op name, start_ns, end_ns): scope path}}``; the
    path is "" where the trace holds none, None where it is ambiguous."""
    out = {}
    for plane in trace["planes"]:
        if plane["name"].startswith(tr.DEVICE_PREFIX):
            out[plane["name"]] = {
                (n, s, s + d): sc for ln in plane["lines"]
                if ln["name"] == tr.OPS_LINE
                for (n, s, d), sc in zip(ln["events"], ln.get(
                    "scopes", [""] * len(ln["events"])))}
    return out


def innermost(spans: list, lo: float, hi: float) -> list:
    """``[(start, end, name or None), ...]`` tiling ``[lo, hi]``: at each
    instant the innermost open span, the latest opened of those still
    open (None where none is)."""
    out, stack, t = [], [], lo

    def advance(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if x > t:
            out.append((t, x, stack[-1][1] if stack else None))
            t = x

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        advance(min(max(s, lo), hi))
        stack.append((e, name))
    advance(hi)
    return out


def charge(intervals: list, segments: list) -> dict:
    """Nanoseconds of the sorted disjoint ``intervals`` that fall in each
    segment's name; ``segments`` is sorted, disjoint and covers them."""
    out, i = {}, 0
    for s, e in intervals:
        while i < len(segments) and segments[i][1] <= s:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < e:
            a, b, name = segments[j]
            ov = min(e, b) - max(s, a)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
            j += 1
    return out


def _inside(t: float, intervals: list, starts: list) -> bool:
    """Whether ``t`` lies in one of the sorted, disjoint ``intervals``."""
    k = bisect.bisect_right(starts, t) - 1
    return k >= 0 and t < intervals[k][1]


def engine(trace: dict) -> dict:
    """What the program's own spans and names give, averaged over the
    device planes, in seconds of the ``bench.window`` span:

    - ``idle_s``: the device's idle time split exactly by the innermost
      open ``engine.*`` host span: ``launch`` (``engine.launch``),
      ``sync`` (``engine.sync``), ``host`` (any other engine span: step
      self time, admit, prefill, emit) and ``harness`` (none open).  The
      four sum to ``window_s - busy_s`` of ``trace_reduce.reduce``.
    - ``decode_s``: device time of the ``engine_decode*`` module events.
      Modules dispatched outside them are not counted: on the per-slot
      path the eager argmax and the stack of the slots' tokens.
    - ``head_s``: device time of the leaf ops whose scope path holds
      ``head``, inside those module events.

    ``spans`` counts the engine spans that overlap the window, and
    ``decode_events`` and ``head_ops`` what the two times are made of, so
    that a reader can tell a program with none of them from a zero;
    ``ambiguous_ops`` counts the leaf ops inside decode modules whose
    scope path could not be told (:func:`op_scopes`), which ``head_s``
    leaves out."""
    lo, hi = tr.window(trace)
    spans = [sp for sp in tr.host_spans(trace)
             if sp[0].startswith(ENGINE_PREFIX)]
    segments = [(a, b, IDLE_KIND.get(n, "host") if n else "harness")
                for a, b, n in innermost(spans, lo, hi)]
    ops, mods = scoped_ops(trace), modules(trace)
    if not ops:
        raise ValueError("the trace holds no device plane")
    idle = dict.fromkeys(("launch", "sync", "host", "harness"), 0.0)
    decode_ns = head_ns = 0.0
    n_decode = n_head = n_ambiguous = 0
    for plane, plane_ops in ops.items():
        busy = tr.union([(s, e) for _, s, e in plane_ops], lo, hi)
        for k, v in charge(tr.gaps(busy, lo, hi), segments).items():
            idle[k] += v
        dec = tr.union([(s, e) for n, s, e in mods.get(plane, ())
                        if DECODE_MODULE in n], lo, hi)
        n_decode += len(dec)
        decode_ns += sum(e - s for s, e in dec)
        starts = [s for s, _ in dec]
        for op in tr.leaves(list(plane_ops)):
            if not _inside(op[1], dec, starts):
                continue
            scope = plane_ops[op]
            if scope is None:
                n_ambiguous += 1
            elif HEAD_SCOPE in scope.split("/"):
                n_head += 1
                head_ns += max(0.0, min(op[2], hi) - max(op[1], lo))
    n = len(ops)
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": {k: v / n / 1e9 for k, v in idle.items()},
        "decode_s": decode_ns / n / 1e9,
        "head_s": head_ns / n / 1e9,
        "spans": sum(1 for _, s, e in spans if s < hi and e > lo),
        "decode_events": n_decode,
        "head_ops": n_head,
        "ambiguous_ops": n_ambiguous,
    }


def trim(trace: dict, seconds: float, start: float = 0.0) -> dict:
    """``seconds`` of the window from ``start`` seconds after it opens,
    the window span cut to them and the events that overlap them kept
    whole (with their scopes): small enough to keep as a recorded
    trace."""
    lo0, hi = tr.window(trace)
    lo = min(hi, lo0 + start * 1e9)
    cut = min(hi, lo + seconds * 1e9)
    planes = []
    for plane in trace["planes"]:
        lines = []
        for ln in plane["lines"]:
            ev, sc = [], []
            scopes = ln.get("scopes", [None] * len(ln["events"]))
            for (n, s, d), scope in zip(ln["events"], scopes):
                if n == tr.WINDOW_SPAN and (s, s + d) == (lo0, hi):
                    ev.append([n, lo, cut - lo])
                elif s < cut and s + d > lo:
                    ev.append([n, s, d])
                else:
                    continue
                sc.append(scope)
            if ev:
                line = {"name": ln["name"], "events": ev}
                if "scopes" in ln:
                    line["scopes"] = sc
                lines.append(line)
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def main(argv=None) -> int:
    (path,) = sys.argv[1:] if argv is None else argv
    trace = (tr.load_json(path) if path.endswith(".json")
             else from_xplane(path))
    print(json.dumps(engine(trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
