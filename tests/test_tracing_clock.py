"""The program's one span (``observability.span``): live while telemetry
is on or a ``jax.profiler`` session is open, written to the profiler's
trace as ``mx:<name>`` and to the ring on the profiler's host clock.

One real profiler session serves the whole file (``start_trace`` costs
seconds); what it captured is asserted test by test.
"""

import glob
import os
import threading
import time

import jax
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu.observability import introspect

N_SPANS = 20


def _mx_events(trace_dir):
    """[(name, start_us, dur_us)] of the ``mx:`` host events."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns / 1e3, e.duration_ns / 1e3)
                    for e in line.events if e.name.startswith("mx:")]
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """Spans entered before, under and after one profiler session, with
    telemetry off throughout."""
    was = obs.set_enabled(False)
    obs.reset()
    cap = {"before": obs.span("clock.before", cat="test")}
    with cap["before"]:
        pass
    cap["ring_before"] = len(obs.tracer())
    d = str(tmp_path_factory.mktemp("prof"))
    try:
        jax.profiler.start_trace(d)
    except Exception as e:  # pragma: no cover - env-specific plugin
        obs.set_enabled(was)
        pytest.skip(f"jax profiler unavailable here: {e}")
    try:
        for i in range(N_SPANS):
            with obs.span("clock.outer", cat="test", i=i) as sp:
                with obs.span("clock.inner", cat="test"):
                    time.sleep(0.0005)
            sp.set(late=i)
        with obs.span("clock.beside", cat="test"):
            cap["t_pc"] = time.perf_counter()
        obs.tracer().record("clock.by_hand", cat="test", ts=cap["t_pc"])
        with introspect.annotate("clock.alias"):
            pass
    finally:
        jax.profiler.stop_trace()
    cap["after"] = obs.span("clock.after", cat="test")
    cap["ring"] = obs.tracer().events()
    cap["xplane"] = _mx_events(d)
    obs.reset()
    obs.set_enabled(was)
    return cap


def _ring(cap, name):
    return [e for e in cap["ring"] if e["name"] == name]


def test_off_and_outside_a_session_the_span_is_the_shared_noop(session):
    assert session["before"] is obs.NO_SPAN
    assert session["after"] is obs.NO_SPAN
    assert session["ring_before"] == 0
    assert not _ring(session, "clock.before")
    assert not _ring(session, "clock.after")


def test_a_span_under_a_session_is_in_the_trace_and_in_the_ring(session):
    names = [n for n, _, _ in session["xplane"]]
    for name in ("clock.outer", "clock.inner"):
        assert names.count("mx:" + name) == N_SPANS
        assert len(_ring(session, name)) == N_SPANS
    assert names.count("mx:clock.alias") == 1  # annotate() is the span
    assert len(_ring(session, "clock.alias")) == 1


def test_ring_and_trace_differ_by_one_constant(session):
    ring = sorted(_ring(session, "clock.outer"), key=lambda e: e["ts"])
    trace = [e for e in session["xplane"] if e[0] == "mx:clock.outer"]
    offsets = [t[1] - r["ts"] for t, r in zip(trace, ring)]
    assert max(offsets) - min(offsets) < 100.0, offsets  # microseconds
    for t, r in zip(trace, ring):
        assert abs(t[2] - r["dur"]) < 100.0


def test_nesting_fills_parent_from_the_threads_open_spans(session):
    outer = {e["id"]: e for e in _ring(session, "clock.outer")}
    inner = _ring(session, "clock.inner")
    assert len({e["id"] for e in session["ring"]}) == len(session["ring"])
    for e in inner:
        parent = outer[e["args"]["parent"]]
        assert parent["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]
    assert all("parent" not in e["args"] for e in outer.values())
    # args set after the span closed still reach its event
    assert sorted(e["args"]["late"] for e in outer.values()) \
        == list(range(N_SPANS))


def test_a_perf_counter_caller_lands_on_the_same_clock(session):
    (hand,) = _ring(session, "clock.by_hand")
    (beside,) = _ring(session, "clock.beside")
    assert abs(hand["ts"] - (beside["ts"] + beside["dur"])) < 1000.0
    assert abs(hand["ts"] - time.time_ns() / 1e3) < 600e6  # epoch us


def test_a_perf_counter_caller_follows_a_stepped_wall_clock(monkeypatch):
    """A long-lived process whose wall clock steps (an NTP correction)
    after the tracer was built: an event handed a perf_counter time
    lands where ``time_ns`` now says, as a :class:`Span` beside it
    would, and not where the clock stood when the tracer was built."""
    tr = obs.Tracer(capacity=8)
    real = time.time_ns
    step_ns = 3600 * 10**9
    monkeypatch.setattr(time, "time_ns", lambda: real() + step_ns)
    tr.record("clock.stepped", cat="test", ts=time.perf_counter())
    (ev,) = tr.events()
    assert abs(ev["ts"] - time.time_ns() / 1e3) < 1e5  # within 0.1 s
    assert ev["ts"] - real() / 1e3 > step_ns / 1e3 - 1e5


def test_each_thread_is_named_by_its_native_id():
    """Two threads alive at once record under distinct ids, each the
    native id of the thread that recorded (no 16-bit fold to collide
    on), as does the caller's own thread."""
    tr = obs.Tracer(capacity=8)
    both = threading.Barrier(2)
    ids = {}

    def record(tag):
        both.wait()  # alive together: the OS cannot reuse an id
        ids[tag] = threading.get_native_id()
        tr.record("clock." + tag, cat="test")
        both.wait()

    threads = [threading.Thread(target=record, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10.0)
    tr.record("clock.main", cat="test")
    ids["main"] = threading.get_native_id()
    got = {ev["name"][len("clock."):]: ev["tid"] for ev in tr.events()}
    assert got == ids and len(set(ids.values())) == 3


def test_telemetry_alone_keeps_the_ring_on_the_epoch_clock():
    was = obs.set_enabled(True)
    obs.reset()
    try:
        with obs.span("clock.enabled", cat="test"):
            pass
        (ev,) = obs.tracer().events()
        assert ev["name"] == "clock.enabled"
        assert abs(ev["ts"] + ev["dur"] - time.time_ns() / 1e3) < 1e6
    finally:
        obs.reset()
        obs.set_enabled(was)


def test_timeline_gives_the_new_categories_tracks_of_their_own(session):
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from tools import timeline

    def ev(name, cat, i, **args):
        return {"name": name, "cat": cat, "ph": "X", "ts": 1.7e15 + i,
                "dur": 5.0, "pid": 1, "tid": 1, "id": i, "args": args}

    doc = timeline.build_timeline(
        [ev("gen.chunk", "generation", 1), ev("req", "request", 2),
         ev("gen.chunk.device", "generation", 3, parent=1),
         ev("spmd.step", "train", 4), ev("clock.outer", "test", 5)]
        + session["ring"][:3])
    tracks = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    where = {e["name"]: tracks[e["tid"]] for e in doc["traceEvents"]
             if e["ph"] == "X"}
    assert where["gen.chunk"] == where["gen.chunk.device"] == "generation"
    assert where["req"] == "request"
    assert where["spmd.step"] == "train loop"
    assert where["clock.outer"] == timeline.MISC_TRACK
    # a parent on the ring's own span stack draws the flow arrow
    assert any(e["ph"] == "s" for e in doc["traceEvents"])
