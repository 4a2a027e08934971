"""The harness end to end on the CPU: each cell's reduced configuration in
interpret mode, through the real generator, serving loop, metric files and
correctness comparison.  The run must be correct and compile nothing in its
window; with the timed path broken underneath, ``correct`` must come out
false."""
from __future__ import annotations

import pytest

from bench import run
from bench.core import spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
SEED = 2**31 + 77


def _run(name, **kw):
    # an open loop needs a window that some arrivals fall in
    offline = spec.cell(name).traffic["arrival"]["kind"] == "offline"
    return run.run_cell(name, SEED, 1.0 if offline else 8.0, False,
                        rehearsal=True, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_with_no_compile_in_window(name):
    r = _run(name)
    assert r["correct"] is True
    assert r["_window"]["compiles"] == 0
    assert r["_window"]["tokens"] > 0
    want = {m["name"] for m in spec.cell(name).end_to_end}
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-3] == "checks"          # last key of the printed line
    assert r["_verdict"]["served_tokens"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_token_altered_where_produced_fails(name, monkeypatch):
    from repro.serving.engine import ServeEngine
    emit = ServeEngine._emit
    vocab = {}

    def altered(self, slot, tok, now):
        v = self.cfg.vocab_size
        vocab["v"] = v
        if slot.n_emitted == 3:            # the fourth token of every request
            tok = (tok + v // 2) % v
        return emit(self, slot, tok, now)
    monkeypatch.setattr(ServeEngine, "_emit", altered)
    r = _run(name)
    assert r["correct"] is False
    assert r["_verdict"]["logit_gap"] > r["_verdict"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_state_left_unchanged_by_decode_fails(name, monkeypatch):
    from repro.serving.engine import ServeEngine
    real = ServeEngine._contig_decode_fn

    def stale(self):
        fn = real(self)

        def keep(toks, poss, cache):
            logits, _ = fn(toks, poss, cache)
            return logits, cache
        return keep
    monkeypatch.setattr(ServeEngine, "_contig_decode_fn", stale)
    r = _run(name)
    assert r["correct"] is False


@pytest.mark.parametrize("name", CELLS)
def test_control_one_precision_lower_reads_wider(name):
    """The control: the reference in fp8 in the program's place.  At this
    size it must read a widest gap at least three times the bf16
    program's, as it does on the chip at the cell's size."""
    v = _run(name, control=True)["_verdict"]
    assert v["control_gap"] > 3 * v["logit_gap"]
    assert v["control_gap"] > 0.05


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_cells_limit(name, monkeypatch):
    """The control judged by the comparison that decides ``correct``: with
    a limit between the program's reading and the control's at this size
    (three times the program's gap), the program comes out correct and the
    fp8 reference in its place does not."""
    cell = spec.cell(name)
    program = _run(name)["_verdict"]["logit_gap"]
    monkeypatch.setitem(cell.own["check"], "logit_gap_limit", 3 * program)
    monkeypatch.setattr(spec, "cell", lambda n: cell)
    v = _run(name, control=True)["_verdict"]
    assert v["correct"] is True
    assert v["control_correct"] is False
