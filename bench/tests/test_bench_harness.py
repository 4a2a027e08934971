"""Latency percentiles with censoring, the peaks table, the cell files,
and the entry point's refusal to run without a TPU."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from bench.core import serve, spec


def _log(due, times, max_new=4, admit=None):
    log = serve.ReqLog(0, due, 8, max_new, rid=0)
    log.times = list(times)
    log.tokens = list(range(len(times)))
    log.admit = admit if admit is not None else (times[0] if times else None)
    return log


def test_percentiles_cover_every_request_and_censor_at_close():
    logs = [_log(1.0 + i, [1.5 + i, 1.6 + i, 1.7 + i, 1.8 + i]) for i in range(5)]
    logs.append(_log(6.0, []))                 # due, never served
    logs.append(_log(0.5, [0.6, 0.7, 0.8, 0.9]))   # due before the window
    lat = serve.window_latencies(logs, 1.0, 10.0)
    assert len(lat["ttft"]) == 6
    assert max(lat["ttft"]) == pytest.approx(4.0)  # 10.0 - 6.0, not dropped
    assert max(lat["wait"]) == pytest.approx(4.0)
    assert serve.percentile(lat["ttft"], 95) > 0.5


def test_stalled_request_counts_its_open_gap():
    log = _log(1.0, [1.1, 1.2], max_new=10)
    lat = serve.window_latencies([log], 1.0, 5.0)
    assert max(lat["itl"]) == pytest.approx(3.8)


def test_percentile_is_over_all_values():
    assert serve.percentile(list(range(101)), 95) == pytest.approx(95.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v99")
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_every_cell_resolves_to_its_files():
    b = spec.benchmark()
    for w in b["workloads"]:
        c = spec.cell(w["name"])
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer
        for m in c.per_layer:
            assert callable(spec.metric_reader(m["name"]))
            assert m["moves"] in names
        assert c.own["check"]["logit_gap_limit"] > 0


def test_run_without_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload", "mamba2.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=spec.ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
