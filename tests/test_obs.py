"""Observability subsystem: span tracer, metrics registry, flight recorder,
MFU helpers, and the trace report tool.

The load-bearing test here is the golden /metrics render: ServeMetrics was
extracted into the shared ``relora_tpu.obs.metrics.MetricsRegistry``, and
the acceptance criterion is that the ``/metrics`` body is **byte-identical**
to the pre-refactor renderer.  The golden string below was captured from the
pre-extraction ``serve/admission.ServeMetrics`` — do not regenerate it from
the current code; that would defeat the pin.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from relora_tpu.obs.flight import FlightRecorder, dump_on_fault
from relora_tpu.obs.metrics import LATENCY_BUCKETS, Histogram, MetricsRegistry
from relora_tpu.obs.mfu import (
    PEAK_FLOPS_DEFAULT,
    peak_flops,
    step_flops_from_cost_analysis,
)
from relora_tpu.obs.tracer import NoopTracer, Tracer, chrome_trace_events, new_trace_id

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# metrics registry: golden render (byte-identical to pre-refactor ServeMetrics)

GOLDEN_RENDER = (
    '# TYPE relora_serve_http_requests_total counter\n'
    'relora_serve_http_requests_total{route="generate"} 2\n'
    'relora_serve_http_requests_total{route="healthz"} 1\n'
    '# TYPE relora_serve_rejected_total counter\n'
    'relora_serve_rejected_total{reason="queue_full"} 1\n'
    '# TYPE relora_serve_requests_finished_total counter\n'
    'relora_serve_requests_finished_total{reason="length"} 2\n'
    '# TYPE relora_serve_tokens_generated_total counter\n'
    'relora_serve_tokens_generated_total 7\n'
    '# TYPE relora_serve_active_slots gauge\n'
    'relora_serve_active_slots 2\n'
    '# TYPE relora_serve_draining gauge\n'
    'relora_serve_draining 0\n'
    '# TYPE relora_serve_queue_depth gauge\n'
    'relora_serve_queue_depth 3\n'
    '# TYPE relora_serve_tpot_seconds histogram\n'
    'relora_serve_tpot_seconds_bucket{le="0.001"} 0\n'
    'relora_serve_tpot_seconds_bucket{le="0.0025"} 0\n'
    'relora_serve_tpot_seconds_bucket{le="0.005"} 0\n'
    'relora_serve_tpot_seconds_bucket{le="0.01"} 0\n'
    'relora_serve_tpot_seconds_bucket{le="0.025"} 1\n'
    'relora_serve_tpot_seconds_bucket{le="0.05"} 1\n'
    'relora_serve_tpot_seconds_bucket{le="0.1"} 1\n'
    'relora_serve_tpot_seconds_bucket{le="0.25"} 1\n'
    'relora_serve_tpot_seconds_bucket{le="0.5"} 1\n'
    'relora_serve_tpot_seconds_bucket{le="1"} 1\n'
    'relora_serve_tpot_seconds_bucket{le="2.5"} 1\n'
    'relora_serve_tpot_seconds_bucket{le="5"} 1\n'
    'relora_serve_tpot_seconds_bucket{le="10"} 1\n'
    'relora_serve_tpot_seconds_bucket{le="30"} 1\n'
    'relora_serve_tpot_seconds_bucket{le="+Inf"} 1\n'
    'relora_serve_tpot_seconds_sum 0.020000\n'
    'relora_serve_tpot_seconds_count 1\n'
    '# TYPE relora_serve_ttft_seconds histogram\n'
    'relora_serve_ttft_seconds_bucket{le="0.001"} 0\n'
    'relora_serve_ttft_seconds_bucket{le="0.0025"} 0\n'
    'relora_serve_ttft_seconds_bucket{le="0.005"} 1\n'
    'relora_serve_ttft_seconds_bucket{le="0.01"} 1\n'
    'relora_serve_ttft_seconds_bucket{le="0.025"} 2\n'
    'relora_serve_ttft_seconds_bucket{le="0.05"} 2\n'
    'relora_serve_ttft_seconds_bucket{le="0.1"} 2\n'
    'relora_serve_ttft_seconds_bucket{le="0.25"} 2\n'
    'relora_serve_ttft_seconds_bucket{le="0.5"} 3\n'
    'relora_serve_ttft_seconds_bucket{le="1"} 3\n'
    'relora_serve_ttft_seconds_bucket{le="2.5"} 4\n'
    'relora_serve_ttft_seconds_bucket{le="5"} 4\n'
    'relora_serve_ttft_seconds_bucket{le="10"} 4\n'
    'relora_serve_ttft_seconds_bucket{le="30"} 4\n'
    'relora_serve_ttft_seconds_bucket{le="+Inf"} 5\n'
    'relora_serve_ttft_seconds_sum 33.321000\n'
    'relora_serve_ttft_seconds_count 5\n'
)


def _populated_serve_metrics():
    # deferred import: pulls in the serve stack (jax) only for the tests
    # that pin the ServeMetrics subclass specifically
    from relora_tpu.serve.admission import ServeMetrics

    m = ServeMetrics()
    m.inc("http_requests_total", ("route", "generate"))
    m.inc("http_requests_total", ("route", "generate"))
    m.inc("http_requests_total", ("route", "healthz"))
    m.inc("tokens_generated_total", by=7)
    m.inc("rejected_total", ("reason", "queue_full"))
    m.inc("requests_finished_total", ("reason", "length"), by=2)
    m.set_gauge("draining", 0)
    m.set_gauge("queue_depth", 3)
    m.set_gauge("active_slots", 2.0)
    for v in (0.004, 0.017, 0.3, 2.0, 31.0):
        m.observe("ttft_seconds", v)
    m.observe("tpot_seconds", 0.02)
    return m


def test_serve_metrics_render_byte_identical_golden():
    assert _populated_serve_metrics().render() == GOLDEN_RENDER


def test_serve_metrics_snapshot_golden():
    assert _populated_serve_metrics().snapshot() == {
        "http_requests_total.generate": 2,
        "http_requests_total.healthz": 1,
        "rejected_total.queue_full": 1,
        "requests_finished_total.length": 2,
        "tokens_generated_total": 7,
        "draining": 0,
        "queue_depth": 3,
        "active_slots": 2.0,
        "ttft_seconds_count": 5,
        "ttft_seconds_sum": 33.321,
        "tpot_seconds_count": 1,
        "tpot_seconds_sum": 0.02,
    }


def test_registry_namespace_and_accessors():
    r = MetricsRegistry(namespace="relora_train")
    r.set_gauge("mfu", 0.42)
    r.inc("steps_total")
    r.observe("metric_pull_seconds", 0.003)
    assert "relora_train_mfu 0.42" in r.render()
    assert r.gauge_value("mfu") == 0.42
    assert r.counter_value("steps_total") == 1
    assert r.histogram("metric_pull_seconds").count == 1
    assert r.histogram("missing") is None


def test_histogram_quantile():
    h = Histogram()
    for v in (0.004, 0.004, 0.004, 0.09, 2.0):
        h.observe(v)
    # p50 of 5 samples lands in the 0.005 bucket; p95 in the 2.5 bucket
    assert h.quantile(0.5) == 0.005
    assert h.quantile(0.95) == 2.5
    assert Histogram().quantile(0.5) == 0.0
    h2 = Histogram()
    h2.observe(100.0)  # beyond the last bound -> +Inf bucket
    assert h2.quantile(0.5) == float("inf")
    assert h.bounds == LATENCY_BUCKETS


# ---------------------------------------------------------------------------
# tracer


def test_span_nesting_builds_a_tree():
    rec = FlightRecorder()
    tr = Tracer(service="t", recorder=rec)
    with tr.span("root", kind="test") as root:
        with tr.span("child_a"):
            with tr.span("grandchild"):
                pass
        with tr.span("child_b"):
            pass
    spans = {s["name"]: s for s in rec.spans()}
    assert set(spans) == {"root", "child_a", "grandchild", "child_b"}
    assert spans["root"]["parent_id"] is None
    assert spans["child_a"]["parent_id"] == spans["root"]["span_id"]
    assert spans["child_b"]["parent_id"] == spans["root"]["span_id"]
    assert spans["grandchild"]["parent_id"] == spans["child_a"]["span_id"]
    # one trace id for the whole tree; attrs and durations recorded
    assert len({s["trace_id"] for s in spans.values()}) == 1
    assert spans["root"]["attrs"] == {"kind": "test"}
    assert all(s["dur_s"] >= 0 for s in spans.values())
    assert root.t_end is not None
    assert tr.current_span() is None  # stack fully unwound


def test_span_end_is_idempotent_and_set_chains():
    rec = FlightRecorder()
    tr = Tracer(service="t", recorder=rec)
    sp = tr.start_span("manual", uid=1)
    d1 = sp.set(outcome="ok").end()
    d2 = sp.end()
    assert d1 == d2
    assert len(rec.spans()) == 1  # recorded exactly once
    assert rec.spans()[0]["attrs"] == {"uid": 1, "outcome": "ok"}


def test_cross_thread_span_with_explicit_parent():
    """The serving pattern: a root span starts on one thread, children are
    attached from another thread via explicit parent= (never the ambient
    stack, which is thread-local)."""
    rec = FlightRecorder()
    tr = Tracer(service="t", recorder=rec)
    rid = new_trace_id()
    root = tr.start_span("request", trace_id=rid, uid=7)

    def worker():
        child = tr.start_span("phase", trace_id=rid, parent=root)
        child.end()

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    root.end()
    spans = {s["name"]: s for s in rec.spans()}
    assert spans["phase"]["parent_id"] == spans["request"]["span_id"]
    assert spans["phase"]["trace_id"] == rid == spans["request"]["trace_id"]
    assert spans["phase"]["thread"] != spans["request"]["thread"]


def test_exception_inside_span_still_records_and_unwinds():
    rec = FlightRecorder()
    tr = Tracer(service="t", recorder=rec)
    with pytest.raises(ValueError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise ValueError("boom")
    assert {s["name"] for s in rec.spans()} == {"outer", "inner"}
    assert tr.current_span() is None


def test_tracer_jsonl_sink(tmp_path):
    path = tmp_path / "spans.jsonl"
    tr = Tracer(service="t", recorder=FlightRecorder(), jsonl_path=str(path))
    with tr.span("a"):
        pass
    tr.event("tick")  # events go to the sink too, tagged so span readers can skip them
    with tr.span("b"):
        pass
    tr.close()
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [rec["name"] for rec in lines] == ["a", "tick", "b"]
    assert lines[1]["_event"] is True
    assert [rec["name"] for rec in lines if not rec.get("_event")] == ["a", "b"]
    with tr.span("after_close"):  # close() drops the sink, not the tracer
        pass
    assert len(path.read_text().splitlines()) == 3


def test_noop_tracer_is_api_compatible():
    tr = NoopTracer()
    with tr.span("x", attr=1) as sp:
        assert sp.end() == 0.0
        assert sp.set(foo="bar") is sp
    sp = tr.start_span("y")
    sp.end()
    tr.event("e")
    tr.close()
    assert tr.current_span() is None
    assert tr.enabled is False


def test_chrome_trace_export():
    rec = FlightRecorder()
    tr = Tracer(service="svc", recorder=rec)
    with tr.span("step", n=3):
        time.sleep(0.001)
    tr.event("marker", note="hi")
    events = chrome_trace_events(rec.spans(), rec.events(), pid=42)
    by_ph = {}
    for e in events:
        by_ph.setdefault(e["ph"], []).append(e)
    (x,) = by_ph["X"]
    assert x["name"] == "step" and x["cat"] == "svc" and x["pid"] == 42
    assert x["dur"] >= 1000  # microseconds
    assert x["args"]["n"] == 3
    (i,) = by_ph["i"]
    assert i["name"] == "marker" and i["args"]["note"] == "hi"
    assert by_ph["M"][0]["args"]["name"]  # thread_name metadata present


# ---------------------------------------------------------------------------
# flight recorder


def test_flight_ring_buffer_bounds_and_dump(tmp_path):
    rec = FlightRecorder(span_capacity=4, event_capacity=2)
    for i in range(7):
        rec.add_span({"name": f"s{i}", "trace_id": "t", "span_id": str(i)})
    rec.add_event({"name": "e"})
    assert [s["name"] for s in rec.spans()] == ["s3", "s4", "s5", "s6"]
    assert rec.dropped_spans == 3
    path = rec.dump(str(tmp_path / "d" / "flight.json"), reason="drill")
    payload = json.loads(open(path).read())
    assert payload["reason"] == "drill"
    assert payload["pid"] == os.getpid()
    assert payload["dropped_spans"] == 3
    assert len(payload["spans"]) == 4 and len(payload["events"]) == 1
    rec.clear()
    assert rec.spans() == [] and rec.dropped_spans == 0


def test_dump_on_fault_env_dir_and_empty_buffer(tmp_path, monkeypatch):
    from relora_tpu.obs import flight

    monkeypatch.setenv("RELORA_TPU_FLIGHT_DIR", str(tmp_path))
    flight.default_recorder().clear()
    assert dump_on_fault("nothing_recorded") is None  # empty buffer -> no file
    Tracer(service="t").start_span("s").end()  # default recorder
    path = dump_on_fault("drill")
    assert path == str(tmp_path / f"flight_drill_{os.getpid()}.json")
    assert json.loads(open(path).read())["reason"] == "drill"
    flight.default_recorder().clear()


# ---------------------------------------------------------------------------
# MFU helpers


class _FakeDevice:
    def __init__(self, kind, platform="tpu"):
        self.device_kind = kind
        self.platform = platform


def test_peak_flops_table_and_env_override(monkeypatch):
    monkeypatch.delenv("RELORA_TPU_PEAK_FLOPS", raising=False)
    # "TPU v5 lite" is what a v5e chip reports as its device_kind
    assert peak_flops(_FakeDevice("TPU v5 lite")) == 197e12
    assert peak_flops(_FakeDevice("TPU v5p chip")) == 459e12
    assert peak_flops(_FakeDevice("TPU v6e")) == 918e12
    assert peak_flops(_FakeDevice("TPU v4")) == 275e12
    assert peak_flops(_FakeDevice("NVIDIA H100 80GB", "gpu")) == 989e12
    assert peak_flops(_FakeDevice("cpu", "cpu")) == PEAK_FLOPS_DEFAULT == 197e12
    monkeypatch.setenv("RELORA_TPU_PEAK_FLOPS", "123e12")
    assert peak_flops(_FakeDevice("NVIDIA H100 80GB", "gpu")) == 123e12  # off-TPU override wins
    assert peak_flops(_FakeDevice("TPU v5 lite")) == 197e12  # a TPU reads the table only


def test_step_flops_from_cost_analysis_shapes():
    assert step_flops_from_cost_analysis({"flops": 5.0}) == 5.0
    assert step_flops_from_cost_analysis({"flops": 5, "bytes accessed": 9.0}) == 5.0
    assert step_flops_from_cost_analysis(None) is None
    assert step_flops_from_cost_analysis({}) is None
    assert step_flops_from_cost_analysis({"flops": 0.0}) is None
    assert step_flops_from_cost_analysis({"bytes": 1}) is None


# ---------------------------------------------------------------------------
# trace report tool


def test_trace_report_renders_dump_and_chrome_export(tmp_path):
    rec = FlightRecorder()
    tr = Tracer(service="train", recorder=rec)
    for step in range(2):
        with tr.span("update_step", step=step):
            with tr.span("data_fetch"):
                pass
            with tr.span("dispatch", step=step):
                time.sleep(0.002)
    dump = rec.dump(str(tmp_path / "flight_manual_1.json"), reason="manual")
    chrome = tmp_path / "chrome.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"), dump,
         "--chrome", str(chrome)],
        capture_output=True, text=True, check=True, cwd=str(tmp_path),
    ).stdout
    assert "reason=manual" in out
    assert "update_step" in out and "dispatch" in out and "data_fetch" in out
    assert "p50_ms" in out and "p95_ms" in out
    events = json.loads(chrome.read_text())["traceEvents"]
    assert any(e["ph"] == "X" and e["name"] == "dispatch" for e in events)


def test_trace_report_reads_jsonl_stream(tmp_path):
    path = tmp_path / "spans.jsonl"
    tr = Tracer(service="t", recorder=FlightRecorder(), jsonl_path=str(path))
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    tr.close()
    with open(path, "a") as fh:
        fh.write('{"torn line')  # killed writer leaves a torn tail: tolerated
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"), str(path)],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "outer" in out and "inner" in out


# ---------------------------------------------------------------------------
# spans on the profiler's clock: annotations, the ``profiled`` mark, the capture


def _load_trace_report():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "tools", "trace_report.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _xplane_under(trace_dir):
    import glob

    [path] = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    return path


def test_span_lands_on_the_host_plane_of_the_profile(tmp_path):
    """A context-managed span inside a jax.profiler session is an event of
    the written xplane's /host:CPU plane, with its name and attributes —
    the ones given at open and the ones ``set`` later; a manual span is not."""
    import jax

    tr = Tracer(service="t", recorder=FlightRecorder())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("round", round=7, note="x", skipped=[1, 2]) as sp:
            with tr.span("pull"):
                pass
            sp.set(dispatches=2)
        tr.start_span("sse_flush").end()
    finally:
        jax.profiler.stop_trace()
    _, spans = _load_trace_report().load_xplane(_xplane_under(tmp_path))
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"round", "pull"}
    rnd, pull = by_name["round"], by_name["pull"]
    assert rnd["attrs"] == {"span_id": sp.span_id, "round": 7, "note": "x", "dispatches": 2}
    # one clock: the child's event lies inside its parent's
    assert rnd["start_ns"] <= pull["start_ns"]
    assert pull["start_ns"] + pull["dur_ns"] <= rnd["start_ns"] + rnd["dur_ns"]


def test_profiled_mark_needs_a_session_at_both_ends(tmp_path):
    """The span inside which a session starts or stops is left out of the
    capture; the spans wholly inside it are kept, and survive the ring."""
    import jax

    rec = FlightRecorder(span_capacity=2)
    tr = Tracer(service="t", recorder=rec)
    with tr.span("before"):
        pass
    with tr.span("straddles_start"):
        jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("inside", n=1):
            with tr.span("child"):
                pass
        manual = tr.start_span("manual")
        manual.end()
        with tr.span("straddles_stop"):
            jax.profiler.stop_trace()
    finally:
        if tr._trace_annotation().is_enabled():
            jax.profiler.stop_trace()
    for _ in range(3):
        with tr.span("after"):
            pass
    marks = {s["name"]: s.get("profiled") for s in [*rec.capture(), *rec.spans()]}
    assert [s["name"] for s in rec.capture()] == ["child", "inside"]
    assert marks["child"] is True and marks["inside"] is True and marks["after"] is False
    # the ring of two has long turned over; the capture has not
    assert [s["name"] for s in rec.spans()] == ["after", "after"]
    assert "profiled" not in manual.to_dict()


def test_capture_is_bounded_counts_drops_and_a_new_session_clears_it(tmp_path):
    rec = FlightRecorder(capture_capacity=3)
    span = lambda name, profiled: {"name": name, "profiled": profiled}  # noqa: E731
    for i in range(5):
        rec.add_span(span(f"a{i}", True))
    rec.add_span({"name": "manual"})  # no mark: says nothing about the session
    assert [s["name"] for s in rec.capture()] == ["a0", "a1", "a2"]
    assert rec.dropped_profiled == 2
    rec.add_span(span("late", None))  # started before the session did
    rec.add_span(span("a5", True))  # still the same session
    assert rec.dropped_profiled == 3
    rec.add_span(span("between", False))  # ended with no session live
    assert [s["name"] for s in rec.capture()] == ["a0", "a1", "a2"]
    rec.add_span(span("b0", True))  # the next session's first span
    assert [s["name"] for s in rec.capture()] == ["b0"] and rec.dropped_profiled == 0
    path = rec.dump(str(tmp_path / "dump.json"))
    with open(path) as fh:
        payload = json.load(fh)
    assert [s["name"] for s in payload["profiled_spans"]] == ["b0"]
    assert payload["dropped_profiled"] == 0
    rec.clear()
    assert rec.capture() == []


def test_tracer_never_imports_jax():
    """In a process that never loads jax the tracer records spans exactly as
    before, and jax is still not loaded afterwards."""
    code = (
        "import sys\n"
        "from relora_tpu.obs.flight import FlightRecorder\n"
        "from relora_tpu.obs.tracer import Tracer\n"
        "rec = FlightRecorder()\n"
        "tr = Tracer(service='router', recorder=rec)\n"
        "with tr.span('outer', a=1) as sp:\n"
        "    with tr.span('inner'):\n"
        "        pass\n"
        "    sp.set(b=2)\n"
        "spans = rec.spans()\n"
        "assert [s['name'] for s in spans] == ['inner', 'outer'], spans\n"
        "assert spans[1]['attrs'] == {'a': 1, 'b': 2} and 'profiled' not in spans[1]\n"
        "assert rec.capture() == []\n"
        "assert 'jax' not in sys.modules and 'jaxlib' not in sys.modules\n"
        "print('ok')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True, timeout=60
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr[-2000:]


def test_a_span_of_another_trace_is_not_adopted_by_the_ambient_span():
    """A request's manual span opened inside a batch-level round keeps its
    own trace and gets no parent from the round; with no trace id of its own
    (or the round's) the ambient span is its parent as before."""
    tr = Tracer(service="t", recorder=FlightRecorder())
    with tr.span("round") as rnd:
        theirs = tr.start_span("decode", trace_id="request-1")
        ours = tr.start_span("insert")
        same = tr.start_span("insert", trace_id=rnd.trace_id)
        with tr.span("prefill_chunk", trace_id="request-1", parent=tr.current_span()) as chunk:
            pass
    assert theirs.parent_id is None and theirs.trace_id == "request-1"
    assert ours.parent_id == rnd.span_id and same.parent_id == rnd.span_id
    assert chunk.parent_id == rnd.span_id and chunk.trace_id == "request-1"


def test_dropped_span_is_not_recorded():
    rec = FlightRecorder()
    tr = Tracer(service="t", recorder=rec)
    with tr.span("round") as sp:
        with tr.span("admit"):
            pass
        sp.drop()
    assert [s["name"] for s in rec.spans()] == ["admit"]
    NoopTracer().span("round").__enter__().drop()  # same surface, nothing to drop


def test_trace_report_puts_idle_gaps_down_to_host_spans():
    """--xplane's reduction on hand-written events: gaps between the union
    of device operations, the innermost span at a gap's midpoint, and a
    round's host-only time."""
    tr = _load_trace_report()
    ms = 1e6
    # busy 0-10 (a loop 0-10 holding its body 2-4), 30-40, 40.5-50, 52-60
    ops = [(0, 10 * ms), (2 * ms, 2 * ms), (30 * ms, 10 * ms), (40.5 * ms, 9.5 * ms), (52 * ms, 8 * ms)]
    assert tr.idle_gaps(ops) == [(10 * ms, 20 * ms), (50 * ms, 2 * ms)]  # the 0.5 ms gap is under the floor
    assert sum(d for _, d in tr.idle_gaps(ops, 0.0)) == 22.5 * ms

    def span(name, start, end, thread="model"):
        return {"name": name, "start_ns": start * ms, "dur_ns": (end - start) * ms, "thread": thread, "attrs": {}}

    spans = sorted(
        [
            span("round", 5, 58), span("admit", 5, 12), span("decode_step", 12, 45),
            span("dispatch", 12, 14), span("pull", 14, 45), span("commit", 45, 55),
            span("round_metrics", 55, 58), span("pull", 46, 47, thread="other"),
        ],
        key=lambda s: s["start_ns"],
    )
    assert tr.innermost_span(spans, 20 * ms)["name"] == "pull"
    assert tr.innermost_span(spans, 51 * ms)["name"] == "commit"
    assert tr.innermost_span(spans, 59 * ms) is None
    [(rnd, inside, host_ns)] = tr.round_host_only(spans)
    # 53 ms of round less dispatch start (12) .. last pull end on its thread (45)
    assert rnd["name"] == "round" and host_ns == (53 - 33) * ms
    assert [s["name"] for s in inside] == ["admit", "decode_step", "dispatch", "pull", "commit", "round_metrics"]
    # by overlap, the 20 ms gap at 10-30 is admit's 2, dispatch's 2 and pull's 16 (its midpoint is in
    # pull, which the midpoint rule bills for all of it); the gap at 50-52 is commit's; a gap past the
    # round's end has no span open
    model = [s for s in spans if s["thread"] == "model"]
    assert tr.idle_by_overlap([(10 * ms, 20 * ms), (50 * ms, 2 * ms), (57 * ms, 3 * ms)], model) == {
        "admit": 2 * ms, "dispatch": 2 * ms, "pull": 16 * ms, "commit": 2 * ms, "round_metrics": 1 * ms, tr.NO_SPAN: 2 * ms,
    }


def test_trace_report_counts_programs_per_whole_round():
    """--xplane's (iii): executions of XLA programs that start inside a whole
    round, per round and by name; what runs outside every round is left out."""
    tr = _load_trace_report()
    rounds = [(10.0, 20.0), (20.0, 30.0)]
    programs = [
        (5.0, "jit_sample_rows"),  # before the first whole round
        (11.0, "jit_prefill_chunk_fn"), (12.0, "jit_decode_paged_fn"), (13.0, "jit_sample_rows"),
        (21.0, "jit_decode_paged_fn"), (22.0, "jit_sample_rows"),
        (30.0, "jit_decode_paged_fn"),  # a round's end is the next one's start
    ]
    per_round = tr.programs_per_frame(programs, rounds)
    assert per_round == {"jit_prefill_chunk_fn": 0.5, "jit_decode_paged_fn": 1.0, "jit_sample_rows": 1.0}
    assert sum(per_round.values()) == 2.5


# ---------------------------------------------------------------------------
# every Pallas kernel carries a name a device trace can be searched for


def _pallas_call_sites():
    import ast

    ops_dir = os.path.join(REPO, "relora_tpu", "ops")
    sites = []
    for fname in sorted(os.listdir(ops_dir)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(ops_dir, fname)) as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"
            ):
                sites.append(pytest.param(node, id=f"{fname}:{node.lineno}"))
    return sites


def _literal_names(call) -> list:
    """The name(s) a ``pallas_call`` can carry: its ``name=`` is a string
    literal, or a choice between string literals (the paged kernel's window
    launches carry a name of their own); anything else gives ``[None]``."""
    import ast

    out = []
    for kw in call.keywords:
        if kw.arg == "name":
            values = [kw.value.body, kw.value.orelse] if isinstance(kw.value, ast.IfExp) else [kw.value]
            out += [v.value if isinstance(v, ast.Constant) and isinstance(v.value, str) else None for v in values]
    return out


@pytest.mark.parametrize("call", _pallas_call_sites())
def test_every_pallas_call_is_named(call):
    """The name reaches the HLO instruction (``%paged_decode_attention.N``),
    which is how the benchmark's trace reduction finds a kernel's time."""
    names = _literal_names(call)
    assert names, "pallas_call without name="
    assert all(n is not None and n.isidentifier() and not n.startswith("_") for n in names), names


def test_pallas_call_names_are_distinct():
    names = [n for p in _pallas_call_sites() for n in _literal_names(p.values[0])]
    assert len(names) == 8 and len(set(names)) == 8, names
    # one kernel at two cache kinds: a pattern for the kernel still finds both launches
    assert {n for n in names if n.startswith("paged_decode_attention")} == {
        "paged_decode_attention", "paged_decode_attention_window"
    }


# ---------------------------------------------------------------------------
# MFU edge cases: the 6ND fallback path and peak-FLOPs resolution corners


def test_peak_flops_device_without_kind_and_env_precedence(monkeypatch):
    monkeypatch.delenv("RELORA_TPU_PEAK_FLOPS", raising=False)
    # off-TPU: no device_kind attribute at all, or an unknown kind -> default
    assert peak_flops(object()) == PEAK_FLOPS_DEFAULT
    assert peak_flops(_FakeDevice("", "cpu")) == PEAK_FLOPS_DEFAULT
    assert peak_flops(_FakeDevice("made-up accelerator 9000", "gpu")) == PEAK_FLOPS_DEFAULT
    # a TPU the table has never heard of is an error, not a default ...
    with pytest.raises(ValueError, match="TPU v9 mega"):
        peak_flops(_FakeDevice("TPU v9 mega"))
    # ... and the env override is no rescue on that path
    monkeypatch.setenv("RELORA_TPU_PEAK_FLOPS", "42e12")
    with pytest.raises(ValueError, match="TPU v9 mega"):
        peak_flops(_FakeDevice("TPU v9 mega"))
    assert peak_flops(object()) == 42e12
    assert peak_flops(None) == 42e12  # the suite's jax.devices()[0] is a CPU


def test_step_flops_from_cost_analysis_hostile_inputs():
    # wrong-typed cost objects must signal fallback, not raise
    assert step_flops_from_cost_analysis(42) is None
    assert step_flops_from_cost_analysis("flops") is None
    assert step_flops_from_cost_analysis({"flops": "NaN-ish"}) is None
    assert step_flops_from_cost_analysis([{"flops": 7.0}]) is None


def test_trainer_measure_step_flops_raises_when_lower_raises():
    """A train step that cannot be lowered is not hidden behind the 6ND
    estimate: _measure_step_flops lets the error through (the step itself
    would fail at its first call anyway, with a worse message)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from relora_tpu.train.trainer import Trainer

    class BadStep:
        def lower(self, *a, **k):
            raise RuntimeError("backend exploded")

    tr = Trainer.__new__(Trainer)  # no __init__: only the fields the method reads
    tr.mesh = Mesh(np.array(jax.devices()).reshape(-1), ("dp",))
    tr._train_step = BadStep()
    tr.state = {"params": np.ones((2,), np.float32)}
    with pytest.raises(RuntimeError, match="backend exploded"):
        tr._measure_step_flops(np.zeros((1, 2, 4), np.int32), jax.random.PRNGKey(0))


def test_trainer_measure_step_flops_honors_live_mfu_kill_switch(monkeypatch):
    from relora_tpu.train.trainer import Trainer

    monkeypatch.setenv("RELORA_TPU_LIVE_MFU", "0")
    tr = Trainer.__new__(Trainer)  # the kill switch returns before any field use
    assert tr._measure_step_flops(None, None) is None


# ---------------------------------------------------------------------------
# placement + collectives: what chip_smoke.py --multichip reads from a run


def test_placement_counts_shard_bytes_per_device():
    """A leaf sharded over four devices counts a quarter on each, a replicated
    leaf in full on each; devices outside the mesh hold nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from relora_tpu.obs.memory import placement

    mesh = Mesh(np.array(jax.devices()[:4]), ("fsdp",))
    sharded = jax.device_put(jnp.zeros((8, 16), jnp.float32), NamedSharding(mesh, P("fsdp")))
    replicated = jax.device_put(jnp.zeros((4,), jnp.float32), NamedSharding(mesh, P()))
    batch = jax.device_put(jnp.zeros((1, 4, 8), jnp.int32), NamedSharding(mesh, P(None, "fsdp")))
    out = placement({"w": sharded, "b": replicated}, batch)
    assert out["device_ids"] == [d.id for d in jax.local_devices()]
    assert out["param_bytes"][:4] == [8 * 16 * 4 // 4 + 16] * 4
    assert out["param_bytes"][4:] == [0] * (len(jax.local_devices()) - 4)
    assert out["batch_devices"] == [d.id for d in jax.devices()[:4]]
    assert out["bytes_in_use"] == [None] * len(jax.local_devices())  # CPU keeps no stats


def test_collective_counts_reads_compiled_text():
    from relora_tpu.obs.memory import collective_counts

    text = """
      %ag = f32[8]{0} all-gather(f32[2]{0} %p), dimensions={0}
      %ars = f32[8]{0} all-reduce-start(f32[8]{0} %x), to_apply=%add
      %ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars)
      %ar2 = f32[8]{0} all-reduce(f32[8]{0} %y), to_apply=%add
      ROOT %t = f32[8]{0} add(%ard, %ar2), metadata={op_name="not an all-gather"}
    """
    assert collective_counts(text) == [["all-gather", 1], ["all-reduce", 2]]
    assert collective_counts("ROOT %t = f32[8]{0} add(%a, %b)") == []
