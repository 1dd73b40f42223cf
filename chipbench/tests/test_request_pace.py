"""The ``request_pace`` reader: over a hand-made ring, and through
``run.drive`` at a tiny size on the CPU."""

import json
import os
import types

import pytest
from helpers import ROOT, drive_tiny

from chipbench.readers import program_spans, request_pace

PACE_METRICS = {"tpot_prefill_stall_share.serve": "stall",
                "tpot_host_turn_share.serve": "host"}


def _decode(rid, ts, dur, tokens=None, device=None, stall=None):
    args = {"rid": rid, "parent": rid}
    if tokens is not None:
        args.update(tokens=tokens, device_us=device, stall_us=stall)
    return {"name": "req.decode", "cat": "request", "ph": "X", "id": rid + 100,
            "ts": ts, "dur": dur, "tid": 1, "args": args}


def _with_ring(monkeypatch, events):
    from mxnet_tpu import observability as obs

    monkeypatch.setattr(obs, "tracer",
                        lambda: types.SimpleNamespace(events=lambda: events))


def _window(seconds):
    return types.SimpleNamespace(trace=types.SimpleNamespace(
        window_s=seconds))


def _read(part, run=None):
    return request_pace.read({"reader": "request_pace", "part": part}, run)


def test_each_share_is_its_part_of_the_requests_mean_pace(monkeypatch):
    # pace 10 and 30 us a token: a mean pace of 20, of which the stalls
    # are (2 + 12) / 2 = 7 and the host's turns (1 + 3) / 2 = 2
    ring = [_decode(1, 0.0, 100.0, tokens=10, device=70.0, stall=20.0),
            _decode(2, 50.0, 60.0, tokens=2, device=30.0, stall=24.0),
            # no tokens after the first: no pace, whatever it carries
            _decode(3, 60.0, 0.0, tokens=0, device=0.0, stall=0.0),
            # the other phases carry no split
            {"name": "req", "cat": "request", "ph": "X", "id": 1, "ts": 0.0,
             "dur": 120.0, "tid": 1, "args": {"rid": 1, "tokens": 11}}]
    _with_ring(monkeypatch, ring)
    assert _read("stall") == pytest.approx(100.0 * 7 / 20)
    assert _read("host") == pytest.approx(100.0 * 2 / 20)


def test_a_ring_without_the_split_reads_nothing(monkeypatch):
    # a program that stamps the phases and not their split: the parent
    _with_ring(monkeypatch, [_decode(1, 0.0, 100.0), _decode(2, 10.0, 80.0)])
    assert _read("stall") is None and _read("host") is None
    _with_ring(monkeypatch, [])
    assert _read("stall") is None and _read("host") is None


def test_only_the_traced_part_of_the_window_counts(monkeypatch):
    # the traced part is the last 1 s (1e6 us) before the ring's newest
    # end, 3e6: a request that began before 2e6 is an older run's
    old = _decode(1, 1.5e6, 1e5, tokens=4, device=0.0, stall=1e5)
    mine = [_decode(2, 2.2e6, 4e5, tokens=4, device=3e5, stall=4e4),
            _decode(3, 2.8e6, 2e5, tokens=2, device=1e5, stall=0.0)]
    _with_ring(monkeypatch, [old] + mine)
    # 1e4 us of stall a token, over 1e5 + 1e5 us of pace a token
    assert _read("stall", _window(1.0)) == pytest.approx(100.0 * 1e4 / 2e5)
    assert _read("host", _window(1.0)) == pytest.approx(
        100.0 * (1.5e4 + 5e4) / 2e5)
    assert [e["args"]["rid"] for e in program_spans.window_events(
        _window(1.0), "request")] == [2, 3]
    # without a trace to clip by, the whole ring
    assert _read("stall") > _read("stall", _window(1.0))


def test_every_entry_names_its_metric_file_and_the_serve_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    serve = [w["name"] for w in bench["workloads"]
             if w["name"] in next(m for m in bench["end_to_end"]
                                  if m["name"] == "tpot_ms_mean")["workloads"]]
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"] in PACE_METRICS}
    assert set(mine) == set(PACE_METRICS)
    for name, part in PACE_METRICS.items():
        m = mine[name]
        assert m["moves"] == "tpot_ms_mean" and m["unit"] == "%"
        assert m["layer"] == "entry points" and m["better"] == "lower"
        assert sorted(m["workloads"]) == sorted(serve)
        with open(os.path.join(ROOT, "chipbench", "metrics",
                               name + ".json")) as f:
            assert json.load(f) == {"reader": "request_pace", "part": part}


def test_a_traced_server_reports_both_shares_and_an_untraced_one_none():
    r = drive_tiny("gpt_tiny", "gpt_tiny.serve", seconds=1.0, trace=1)
    assert r["correct"], r["compared"]
    m = r["metrics"]
    stall = m["tpot_prefill_stall_share.serve"]["value"]
    host = m["tpot_host_turn_share.serve"]["value"]
    assert 0 <= stall < 100 and 0 < host < 100 and stall + host < 100
    # the split is of the window's own requests: each one's parts lie
    # within its pace
    run = types.SimpleNamespace(trace=types.SimpleNamespace(
        window_s=r["device"]["window_s"]))
    decodes = [e for e in program_spans.window_events(run, "request")
               if e["name"] == "req.decode" and e["args"]["tokens"]]
    assert decodes and all(
        0 < e["args"]["device_us"]
        and e["args"]["device_us"] + e["args"]["stall_us"] <= e["dur"] + 1.0
        for e in decodes)
    r = drive_tiny("gpt_tiny", "gpt_tiny.serve", seconds=0.5, trace=0)
    assert r["correct"], r["compared"]
    assert not set(PACE_METRICS) & set(r["metrics"])


def test_a_training_run_reports_neither():
    r = drive_tiny("bert_tiny", "bert_tiny.spmd", trace=1)
    assert not set(PACE_METRICS) & set(r["metrics"])
