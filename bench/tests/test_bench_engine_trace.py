"""The reading of the program's own spans and names (``bench/engine_trace``):
the idle split by innermost engine span and the decode and head device
times on a synthetic trace with known answers, the scope paths read from
an ``.xplane.pb``'s wire format, and a trace recorded on a TPU v5e around
one iteration boundary of ``mamba2.decode``
(``data/mamba2.decode.engine_spans.json``, op names shortened)."""
from __future__ import annotations

import os

import pytest

from bench import engine_trace as et
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000


def _synthetic():
    """One engine step (admit holding a prefill, launch, sync, emit) in a
    100 ms window, decode and prefill module events, and each op's scope
    path.  Idle in the window: [0, 10], [40, 50], [55, 95] ms."""
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.window", 0, 100 * MS],
            ["bench.step", 0, 60 * MS],
            ["bench.wait", 60 * MS, 40 * MS],
            ["engine.step", 2 * MS, 56 * MS],
            ["engine.admit", 2 * MS, 4 * MS],
            ["engine.prefill", 3 * MS, 2 * MS],
            ["engine.launch", 8 * MS, 12 * MS],
            ["engine.sync", 20 * MS, 30 * MS],
            ["engine.emit", 50 * MS, 6 * MS]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["matmul.1", 10 * MS, 20 * MS],
                ["matmul.2", 20 * MS, 20 * MS],      # overlaps the first
                ["decode", 50 * MS, 5 * MS],
                ["late", 95 * MS, 10 * MS]],         # runs past the window
             "scopes": ["jit(engine_decode)/headroom/dot:",
                        "jit(engine_decode)/head/dot_general:",
                        "jit(engine_prefill)/head/pad:",
                        "jit(engine_decode)/head/jit(_pad)/pad:"]},
            {"name": "XLA Modules", "events": [
                ["jit_engine_decode(7)", 10 * MS, 30 * MS],
                ["jit_engine_prefill(3)", 50 * MS, 5 * MS],
                ["jit_engine_decode(7)", 95 * MS, 10 * MS]]}]},
    ]}


def test_idle_split_by_innermost_span():
    """Each idle interval is split exactly by the innermost open engine
    span: [0, 2] harness, [2, 8] host (admit, prefill, admit, step self
    time), [8, 10] launch; [40, 50] sync; [55, 58] host (emit, step),
    [58, 95] harness.  The four parts sum to the idle time ``reduce``
    gives, from which ``device.idle_share.batch`` is read."""
    t = _synthetic()
    e, r = et.engine(t), tr.reduce(t)
    assert e["idle_s"] == pytest.approx(
        {"launch": 0.002, "sync": 0.010, "host": 0.009, "harness": 0.039})
    assert abs(sum(e["idle_s"].values())
               - (r["window_s"] - r["busy_s"])) < 1e-9
    assert e["spans"] == 6 and e["window_s"] == pytest.approx(0.1)


def test_decode_and_head_time():
    """Decode time is the decode modules' time clipped to the window;
    head time is that of the leaf ops whose scope path holds ``head`` as
    a whole component, inside a decode module: not the prefill's head op,
    and not ``headroom``."""
    e = et.engine(_synthetic())
    assert e["decode_s"] == pytest.approx(0.030 + 0.005)
    assert e["decode_events"] == 2
    assert e["head_s"] == pytest.approx(0.020 + 0.005)
    assert e["head_ops"] == 2 and e["ambiguous_ops"] == 0


def test_ambiguous_scopes_are_counted_not_charged():
    t = _synthetic()
    t["planes"][1]["lines"][0]["scopes"][1] = None
    e = et.engine(t)
    assert e["ambiguous_ops"] == 1 and e["head_ops"] == 1
    assert e["head_s"] == pytest.approx(0.005)


def test_a_program_without_spans_or_names_reads_zero_events():
    """A program that records no engine spans, module names or scopes
    gives counts of zero, from which a reader tells it from a zero time."""
    t = _synthetic()
    host, dev = t["planes"]
    host["lines"][0]["events"] = [ev for ev in host["lines"][0]["events"]
                                  if not ev[0].startswith("engine.")]
    dev["lines"] = [{"name": "XLA Ops", "events": dev["lines"][0]["events"]}]
    e = et.engine(t)
    assert (e["spans"], e["decode_events"], e["head_ops"]) == (0, 0, 0)
    assert e["idle_s"]["harness"] == pytest.approx(0.060)


def test_innermost_tiles_the_window():
    spans = [("a", 0, 10), ("b", 2, 4), ("c", 4, 6), ("d", 12, 20)]
    assert et.innermost(spans, 1, 15) == [
        (1, 2, "a"), (2, 4, "b"), (4, 6, "c"), (6, 10, "a"),
        (10, 12, None), (12, 15, "d")]


def test_trim_from_a_start_keeps_scopes_beside_their_events():
    t = et.trim(_synthetic(), 0.02, start=0.025)
    assert tr.window(t) == (25 * MS, 45 * MS)
    ops = [ln for ln in t["planes"][1]["lines"] if ln["name"] == tr.OPS_LINE][0]
    assert [e[0] for e in ops["events"]] == ["matmul.1", "matmul.2"]
    assert ops["scopes"] == ["jit(engine_decode)/headroom/dot:",
                             "jit(engine_decode)/head/dot_general:"]


# -- scope paths from the protobuf ------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, ops: list) -> bytes:
    """An XPlane whose event metadata records are ``ops``: ``(op name,
    tf_op scope or None)``; one more stat name that is not ``tf_op``."""
    body = _field(2, name)
    for mid, (op, scope) in enumerate(ops, 1):
        md = _field(1, mid) + _field(2, op)
        if scope is not None:
            md += _field(5, _field(1, 7) + _field(5, scope))
        md += _field(5, _field(1, 8) + _field(5, "other"))
        body += _field(4, _field(1, mid) + _field(2, md))
    for sid, sname in ((7, "tf_op"), (8, "long_name")):
        body += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                  + _field(2, sname)))
    return body


def test_op_scopes_from_the_wire_format(tmp_path):
    """An op name's scope path is its records' ``tf_op`` stat; records of
    several programs that agree past their ``jit(<module>)`` keep it, ones
    that differ make it None, and ops with no path are left out."""
    space = _field(1, _plane("/device:TPU:0", [
        ("fusion.1", "jit(engine_decode)/head/dot_general"),
        ("fusion.1", "jit(engine_prefill)/head/dot_general"),
        ("pad.2", "jit(engine_decode)/head/pad"),
        ("pad.2", "jit(_argmax)/argmax"),
        ("copy.3", None),
        ("copy.4", "jit(engine_decode)/copy"),
        ("copy.4", None)]))
    space += _field(1, _plane("/host:CPU", [("fusion.1", "x")]))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert et.op_scopes(str(path)) == {"/device:TPU:0": {
        "fusion.1": "jit(engine_decode)/head/dot_general",
        "pad.2": None, "copy.4": None}}


# -- recorded on the chip ----------------------------------------------------

RECORDED = os.path.join(DATA, "mamba2.decode.engine_spans.json")


def test_recorded_engine_trace():
    """A trace recorded on a TPU v5e around one iteration boundary of
    ``mamba2.decode``: the split partitions the idle time, the program's
    readings are there, and the host's and device's clocks agree: every
    ``engine_decode`` module event starts inside an ``engine.step``, after
    that step's ``engine.launch`` opened."""
    assert os.path.getsize(RECORDED) < 1_000_000
    t = tr.load_json(RECORDED)
    r, e = tr.reduce(t), et.engine(t)
    assert abs(sum(e["idle_s"].values()) - (r["window_s"] - r["busy_s"])) < 1e-9
    assert all(v >= 0 for v in e["idle_s"].values())
    assert e["decode_events"] and e["head_ops"] and e["spans"]
    assert e["ambiguous_ops"] == 0
    assert 0 < e["head_s"] < e["decode_s"] <= r["busy_s"] * (1 + 1e-9)
    spans = tr.host_spans(t)
    steps = sorted((s, e_) for n, s, e_ in spans if n == "engine.step")
    launches = sorted(s for n, s, _ in spans if n == "engine.launch")
    assert len(steps) >= 2                    # an iteration boundary
    (mods,) = et.modules(t).values()
    checked = 0
    for name, s, _ in mods:
        if et.DECODE_MODULE not in name:
            continue
        (step,) = [st for st in steps if st[0] <= s < st[1]]
        opened = [l for l in launches if step[0] <= l < step[1]]
        if opened:
            assert s > opened[0]
            checked += 1
    assert checked


def test_cli_reads_a_recorded_trace(capsys):
    import json
    assert et.main([RECORDED]) == 0
    assert json.loads(capsys.readouterr().out) == et.engine(
        tr.load_json(RECORDED))
