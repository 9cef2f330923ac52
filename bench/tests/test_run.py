"""Whole runs of the harness on the CPU at a tiny size, and the faults
its comparison must catch.

`run.run` is driven directly: it skips the harness's look for a chip
(which fails on the CPU by design) and does the rest of a run — build,
warm-up, the measured loop through the frontend, the reference check and
the result line.  The fault runs break the timed path underneath, where
the answers are produced (`RuntimeBackend._finish`, the host end of every
dispatch), and must come out `correct: false`.
"""

from __future__ import annotations

import dataclasses
import json
import types

import numpy as np
import pytest

import run
import traffic

PEAK = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


@pytest.fixture
def small_traffic(monkeypatch):
    """CPU-sized rates for the committed mixes."""
    real = traffic.load

    def load(mix, cell):
        t = real(mix, cell)
        if t.loop == "open":
            return dataclasses.replace(t, rate_qps=150.0)
        return dataclasses.replace(t, clients=32, max_qps=40000.0)

    monkeypatch.setattr(traffic, "load", load)


def drive(cell_name: str, seed: int, trace: int, capsys, seconds=2.0):
    import jax

    spec, cell = run.cell_spec(cell_name)
    args = types.SimpleNamespace(workload=cell_name, seed=seed,
                                 seconds=seconds, trace=trace)
    assert run.run(args, spec, cell, jax.devices()[:1], PEAK) == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    return result, err, spec


@pytest.mark.parametrize("cell,trace", [
    ("glove100-dot.fresh-open", 0),
    ("glove100-dot.fresh-closed", 0),
    ("glove100-dot.fresh-closed", 1),
])
def test_run_is_correct_and_well_formed(tiny, small_traffic, capsys, cell,
                                        trace):
    result, err, spec = drive(cell, 2**32 + 5, trace, capsys)
    assert result["correct"] is True
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["answer_gap"]["value"] <= \
        result["checks"]["answer_gap"]["limit"]
    assert err.strip().splitlines()[-1].startswith("check answer_gap")
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert result["metrics"]["frontend.batch_fill_pct.tput"][
            "value"] == 100.0
    else:
        want = {m["name"] for m in run.metrics_for(spec, cell, "end_to_end")}
        assert set(result["metrics"]) == want
        for m in result["metrics"].values():
            assert m["value"] > 0


def test_zipf_mix_served_from_the_cache_is_correct(tiny, monkeypatch, capsys):
    """A mix made of data alone: a hot pool answered mostly by the
    frontend's result cache still compares correct."""
    monkeypatch.setattr(traffic, "load", lambda mix, cell: traffic.from_spec(
        {"loop": "open", "rate_qps": 150.0, "arrivals": {"kind": "poisson"},
         "queries": {"kind": "zipf-pool", "pool": 40, "s": 1.2}}))
    result, _, _ = drive("glove100-dot.fresh-open", 2**31 + 77, 0, capsys)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] == 300


def _alter_one_id(orig):
    def finish(self, raw, ex_pad, m, distributed):
        ids, scores, stats = orig(self, raw, ex_pad, m, distributed)
        ids = np.array(ids)
        ids[0, 0] = (ids[0, 0] + 1) % 3000 if ids[0, 0] >= 0 else 0
        return ids, scores, stats
    return finish


def _drop_half_the_batch(orig):
    def finish(self, raw, ex_pad, m, distributed):
        ids, scores, stats = orig(self, raw, ex_pad, m, distributed)
        ids, scores = np.array(ids), np.array(scores)
        half = ids.shape[0] // 2
        ids[half:] = -1
        scores[half:] = -np.inf
        return ids, scores, stats
    return finish


def _lower_score_precision(orig):
    def finish(self, raw, ex_pad, m, distributed):
        import ml_dtypes

        ids, scores, stats = orig(self, raw, ex_pad, m, distributed)
        scores = np.asarray(scores).astype(ml_dtypes.bfloat16).astype(
            np.float32)
        return ids, scores, stats
    return finish


@pytest.mark.parametrize("fault", [_alter_one_id, _drop_half_the_batch,
                                   _lower_score_precision])
def test_broken_timed_path_is_not_correct(tiny, small_traffic, capsys,
                                          monkeypatch, fault):
    from repro.serve.frontend import RuntimeBackend

    monkeypatch.setattr(RuntimeBackend, "_finish",
                        fault(RuntimeBackend._finish))
    result, err, _ = drive("glove100-dot.fresh-closed", 11, 0, capsys,
                           seconds=1.0)
    assert result["correct"] is False
    assert result["checks"]["answer_gap"]["value"] > \
        result["checks"]["answer_gap"]["limit"]
