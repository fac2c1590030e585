"""Observability subsystem: tail/follow reader robustness, the
forward-compatible StreamDecoder (version check + skipped-unknown
accounting), the recorder's live-sink/bounded-ring memory contract,
Chrome trace-event export, the operator console's headless render over
the committed chaos_partition golden stream, and the byte-identity
contract: a golden scenario run with telemetry + tracing + runtime
records enabled must still verify against its committed golden."""
import glob
import json
import os
import threading
import time
from collections import Counter

import pytest

from repro.obs.console import ConsoleState, render, sparkline
from repro.obs.spans import (
    NULL_TRACER, PROGRAM_BUILD_EVENT, SpanTracer, program_build_listener,
    validate_chrome_trace,
)
from repro.obs.tail import TailReader, read_complete_lines
from repro.telemetry import (
    DEFAULT_WINDOW, RunMeta, RuntimeMetrics, StreamDecoder,
    TelemetryRecorder, schema,
)

GOLDEN_STREAM = os.path.join(os.path.dirname(__file__), os.pardir,
                             "results", "golden", "streams",
                             "chaos_partition.jsonl")


# ---------------------------------------------------------------------------
# Tail / follow reader
# ---------------------------------------------------------------------------

def test_tail_holds_back_partial_trailing_line(tmp_path):
    p = tmp_path / "s.jsonl"
    p.write_text('{"a": 1}\n{"b": 2')          # second record still mid-write
    r = TailReader(str(p))
    assert r.read_available() == ['{"a": 1}']
    assert r.read_available() == []             # partial line stays buffered
    with open(p, "a") as f:
        f.write('}\n{"c": 3}\n')
    assert r.read_available() == ['{"b": 2}', '{"c": 3}']
    r.close()


def test_tail_restarts_on_truncation(tmp_path):
    p = tmp_path / "s.jsonl"
    p.write_text("one\ntwo\nthree\n")
    r = TailReader(str(p))
    assert r.read_available() == ["one", "two", "three"]
    p.write_text("fresh\n")                     # rerun over the same path
    assert r.read_available() == ["fresh"]
    r.close()


def test_tail_follows_rotation_to_new_inode(tmp_path):
    p = tmp_path / "s.jsonl"
    p.write_text("old\n")
    r = TailReader(str(p))
    assert r.read_available() == ["old"]
    os.rename(p, tmp_path / "s.jsonl.1")        # rotate
    (tmp_path / "s.jsonl").write_text("new\n")
    # allow same-inode reuse on exotic filesystems: poll a couple times
    got = r.read_available() or r.read_available()
    assert got == ["new"]
    r.close()


def test_tail_waits_for_missing_file(tmp_path):
    p = tmp_path / "later.jsonl"
    r = TailReader(str(p))
    assert r.read_available() == []             # not an error
    p.write_text("here\n")
    assert r.read_available() == ["here"]
    r.close()


def test_follow_drains_after_stop_and_survives_concurrent_writer(tmp_path):
    p = tmp_path / "s.jsonl"
    p.write_text("")
    stop = threading.Event()
    got = []

    def writer():
        with open(p, "a") as f:
            for i in range(20):
                f.write(f"line-{i}\n")
                f.flush()
                time.sleep(0.002)
        stop.set()

    t = threading.Thread(target=writer)
    t.start()
    r = TailReader(str(p), poll=0.005)
    for ln in r.follow(stop=stop.is_set):
        got.append(ln)
    t.join()
    r.close()
    # final drain after stop => nothing written before stop is lost
    assert got == [f"line-{i}" for i in range(20)]


def test_read_complete_lines_drops_partial_tail(tmp_path):
    p = tmp_path / "s.jsonl"
    p.write_text("a\nb\ncut-off-no-newline")
    assert read_complete_lines(str(p)) == ["a", "b"]


# ---------------------------------------------------------------------------
# StreamDecoder: forward-compat version check + drift accounting
# ---------------------------------------------------------------------------

def _meta_line(version: int) -> str:
    d = json.loads(schema.to_json_line(RunMeta(
        method="heloco", engine="sim", n_workers=2, outer_steps=4, seed=0)))
    d["schema_version"] = version
    return json.dumps(d)


def test_decoder_counts_unknown_kinds_and_keys_from_newer_stream():
    dec = StreamDecoder()
    assert dec.decode(_meta_line(schema.SCHEMA_VERSION + 1)) is not None
    assert dec.newer_stream
    # a record kind this reader has never heard of
    assert dec.decode('{"kind": "gpu_power", "watts": 412.0}') is None
    # a known kind with a field from the future
    line = json.dumps({"kind": "eval", "outer_step": 4, "sim_time": 1.0,
                       "wall_time": 2.0, "mean_loss": 3.5,
                       "per_lang": {}, "perplexity_v4": 33.1})
    rec = dec.decode(line)
    assert rec is not None and rec.mean_loss == 3.5
    assert dec.unknown_kinds == {"gpu_power": 1}
    assert dec.unknown_keys == {"eval.perplexity_v4": 1}
    report = "\n".join(dec.drift_report())
    assert f"v{schema.SCHEMA_VERSION + 1} > reader" in report
    assert "gpu_power" in report and "eval.perplexity_v4" in report


def test_decoder_strict_raises_on_same_version_drift_only():
    strict = StreamDecoder(strict=True)
    strict.decode(_meta_line(schema.SCHEMA_VERSION))
    with pytest.raises(ValueError, match="unknown"):
        strict.decode('{"kind": "gpu_power", "watts": 1.0}')
    # ... but a declared-NEWER stream is tolerated-and-counted even strict
    newer = StreamDecoder(strict=True)
    newer.decode(_meta_line(schema.SCHEMA_VERSION + 2))
    assert newer.decode('{"kind": "gpu_power", "watts": 1.0}') is None
    assert newer.unknown_kinds["gpu_power"] == 1


def test_decoder_tolerates_bad_lines_and_missing_required_fields():
    dec = StreamDecoder()
    assert dec.decode("") is None
    assert dec.decode('{"kind": "arrival"') is None          # torn JSON
    assert dec.decode('{"kind": "eval", "outer_step": 1}') is None  # missing
    assert dec.bad_lines == 2
    assert any("undecodable" in s for s in dec.drift_report())


# ---------------------------------------------------------------------------
# Recorder: live sink + bounded ring (the memory contract)
# ---------------------------------------------------------------------------

def _fake_arrival(i):
    class A:
        outer_step = i
        worker_id = i % 2
        staleness = 0
        rho = 1.0
        sim_time = float(i)
        lang = "en"
        dropped = False
    return A()


def test_recorder_sink_streams_full_stream_but_bounds_memory(tmp_path):
    sink = str(tmp_path / "live.jsonl")
    rec = TelemetryRecorder(sink=sink, window=8)
    rec.ensure_meta(method="heloco", engine="sim", n_workers=2,
                    outer_steps=64, seed=0)
    for i in range(64):
        rec.record_arrival(_fake_arrival(i))
    assert len(rec.records) == 8                 # bounded ring
    # ... but the on-disk stream is complete and live (no close needed)
    lines = read_complete_lines(sink)
    assert len(lines) == 65                      # meta + 64 arrivals
    # write_jsonl copies the FULL stream, not the ring
    out = str(tmp_path / "copy.jsonl")
    rec.write_jsonl(out)
    assert len(read_complete_lines(out)) == 65
    rec.close()
    rec.close()                                  # idempotent
    dec = StreamDecoder(strict=True)
    for ln in lines:
        assert dec.decode(ln) is not None
    assert dec.meta is not None and not dec.drift_report()


def test_recorder_without_sink_keeps_unbounded_list():
    rec = TelemetryRecorder()
    for i in range(DEFAULT_WINDOW + 10):
        rec.record_arrival(_fake_arrival(i))
    assert isinstance(rec.records, list)
    assert len(rec.records) == DEFAULT_WINDOW + 10


def test_runtime_record_roundtrip():
    rec = TelemetryRecorder()
    rec.record_runtime(outer_step=7, sim_time=3.0, workers_alive=3,
                       workers_total=4, queue_depth=2,
                       liveness={"dead": 1},
                       delivery={"retries": 5.0})
    (rt,) = rec.runtime_records()
    line = schema.to_json_line(rt)
    back = schema.from_json_line(line)
    assert isinstance(back, RuntimeMetrics)
    assert back.workers_alive == 3 and back.delivery == {"retries": 5.0}


# ---------------------------------------------------------------------------
# Span tracer + Chrome trace export
# ---------------------------------------------------------------------------

def test_span_tracer_exports_valid_chrome_trace_with_thread_names():
    tr = SpanTracer()
    with tr.span("outer", cat="engine", step=1):
        with tr.span("inner", cat="compute"):
            pass
    tr.instant("retry", cat="transport", wid=3)

    def worker():
        with tr.span("worker_round", cat="compute", wid=0):
            pass

    t = threading.Thread(target=worker, name="heloco-worker-0")
    t.start()
    t.join()
    assert len(tr) == 4
    doc = tr.to_chrome()
    assert validate_chrome_trace(doc) == []
    names = [e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert "heloco-worker-0" in names
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in spans} == {"outer", "inner", "worker_round"}
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
    # nesting: inner ends no later than outer
    by = {e["name"]: e for e in spans}
    assert (by["inner"]["ts"] + by["inner"]["dur"]
            <= by["outer"]["ts"] + by["outer"]["dur"] + 1e-3)


def test_span_tracer_write_roundtrip(tmp_path):
    tr = SpanTracer()
    with tr.span("s"):
        pass
    path = tr.write(str(tmp_path / "t.trace.json"))
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) == []


def test_null_tracer_is_inert():
    with NULL_TRACER.span("anything", wid=1):
        pass
    NULL_TRACER.instant("x")
    assert len(NULL_TRACER) == 0
    with pytest.raises(RuntimeError):
        NULL_TRACER.write("/nonexistent/nope.json")


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace({}) != []
    assert validate_chrome_trace({"traceEvents": []}) != []
    no_dur = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0,
                               "pid": 0, "tid": 0}]}
    assert any("dur" in p for p in validate_chrome_trace(no_dur))
    meta_only = {"traceEvents": [{"name": "process_name", "ph": "M",
                                  "pid": 0, "args": {"name": "p"}}]}
    assert any("no complete" in p for p in validate_chrome_trace(meta_only))


# ---------------------------------------------------------------------------
# Spans inside the inner round, program builds, the profiler's clock
# ---------------------------------------------------------------------------

#: the spans of each of the H steps of a round
PER_STEP = ("batch_sample", "batch_to_device", "inner_dispatch")
IN_ROUND = PER_STEP + ("round_copy", "pseudo_gradient")


def _round_job(compression="none", outer_steps=8):
    """Two workers (paces 1 and 2), H = 2, one arrival per commit, a
    one-layer model."""
    import dataclasses
    cfg = _tiny_cfg(commit_batch=1, outer_steps=outer_steps, inner_steps=2,
                    compression=compression)
    return dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, n_layers=1))


def _traced_run(eng, capture=None):
    """``eng.run()`` inside ``capture`` (a context manager), with the
    lowerings during the run counted by a listener of the test's own."""
    import contextlib

    from jax import monitoring
    lowered = []

    def count(event, duration, **kw):
        if event == PROGRAM_BUILD_EVENT:
            lowered.append(kw["fun_name"])

    with capture or contextlib.nullcontext():
        monitoring.register_event_duration_secs_listener(count)
        try:
            eng.run()
        finally:
            monitoring.unregister_event_duration_listener(count)
    return lowered


def _spans(tr):
    return [e for e in tr.to_chrome()["traceEvents"] if e.get("ph") == "X"]


def _within(child, parent):
    """``child`` lies inside ``parent`` on the same thread (trace us)."""
    return (child["tid"] == parent["tid"] and child["ts"] >= parent["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 1e-3)


def _after_warmup(tr):
    """The ``program_build`` and ``worker_round`` spans that start after
    both workers have committed once."""
    spans = _spans(tr)
    warm = sorted(e["ts"] + e["dur"] for e in spans
                  if e["name"] == "server_commit")[1]
    return ([e for e in spans if e["name"] == "program_build"
             and e["ts"] > warm],
            [e for e in spans if e["name"] == "worker_round"
             and e["ts"] > warm])


@pytest.fixture(scope="module")
def traced_fp32(tmp_path_factory):
    """A tiny fp32 sim run with a ``SpanTracer``, inside a CPU profiler
    capture: the engine, its tracer, the lowerings an independent
    listener counted during ``run``, and the capture's directory."""
    import jax

    from repro.async_engine.engine import make_engine
    tr = SpanTracer()
    eng = make_engine(_round_job(), tracer=tr)
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    lowered = _traced_run(eng, jax.profiler.trace(out,
                                                  profiler_options=opts))
    return eng, tr, lowered, out


def test_worker_round_nests_sampling_transfer_dispatch_copy_and_delta(
        traced_fp32):
    """Per ``worker_round``: H ``batch_sample``, ``batch_to_device`` and
    ``inner_dispatch`` spans and one ``round_copy`` and
    ``pseudo_gradient`` inside it; one ``round_dispatch`` per dispatch,
    outside every round."""
    eng, tr, _, _ = traced_fp32
    spans = _spans(tr)
    rounds = [e for e in spans if e["name"] == "worker_round"]
    assert len(rounds) == eng.cfg.outer_steps
    totals = Counter(e["name"] for e in spans)
    for r in rounds:
        h = r["args"]["h"]
        assert h == eng.cfg.inner_steps
        inside = Counter(e["name"] for e in spans
                         if e is not r and _within(e, r))
        assert {n: inside[n] for n in IN_ROUND} == {
            **dict.fromkeys(PER_STEP, h), "round_copy": 1,
            "pseudo_gradient": 1}
    assert all(totals[n] == totals["worker_round"] * eng.cfg.inner_steps
               for n in PER_STEP)
    assert totals["round_copy"] == totals["pseudo_gradient"] == len(rounds)
    dispatches = [e for e in spans if e["name"] == "round_dispatch"]
    in_flight = sum(w.in_flight for w in eng.workers.values())
    assert len(dispatches) == len(rounds) + in_flight
    assert not any(_within(d, r) for d in dispatches for r in rounds)


def test_profiler_capture_holds_the_round_spans_on_the_host_plane(
        traced_fp32):
    """The program's spans are ``TraceAnnotation``s of a ``jax.profiler``
    capture: on ``/host:CPU``, one per span, each inside a round."""
    from jax.profiler import ProfileData
    _, tr, _, out = traced_fp32
    files = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    assert files, "the capture wrote no profile"
    host = [(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(files[-1]).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]
    got = Counter(name for name, _, _ in host)
    want = Counter(e["name"] for e in _spans(tr))
    for name in IN_ROUND + ("worker_round", "round_dispatch"):
        assert got[name] == want[name] > 0, name
    rounds = [(s, e) for name, s, e in host if name == "worker_round"]
    for name, s, e in host:
        if name in IN_ROUND:
            assert any(a <= s and e <= b for a, b in rounds), name


def test_program_builds_equal_the_lowerings_and_stop_after_warmup(
        traced_fp32):
    """An fp32 job lowers nothing once both workers have committed, and
    every lowering during ``run`` is one ``program_build`` span."""
    _, tr, lowered, _ = traced_fp32
    builds = [e for e in _spans(tr) if e["name"] == "program_build"]
    assert builds and sorted(e["args"]["fun"] for e in builds) \
        == sorted(lowered)
    late_builds, late_rounds = _after_warmup(tr)
    assert late_rounds and late_builds == []


def test_packed_int8_job_builds_programs_every_round():
    """The packed int8 round trip is one program, lowered twice in a run
    at most (a worker's first round carries no error feedback, its later
    rounds do) and not every round: after warm-up at most its second
    trace remains, each ``program_build`` a lowering."""
    from repro.async_engine.engine import make_engine
    tr = SpanTracer()
    lowered = _traced_run(make_engine(_round_job("int8"), tracer=tr))
    builds = [e for e in _spans(tr) if e["name"] == "program_build"]
    assert sorted(e["args"]["fun"] for e in builds) == sorted(lowered)
    assert lowered.count("jit(_packed_int8_program)") <= 2  # 0: cached
    late_builds, late_rounds = _after_warmup(tr)
    assert len(late_rounds) > 2
    assert [e["args"]["fun"] for e in late_builds] in (
        [], ["jit(_packed_int8_program)"])


def test_build_listener_only_inside_a_traced_run(monkeypatch, traced_fp32):
    """No listener is ever registered under ``NULL_TRACER``; a traced
    run's listener is gone once ``run`` returns or raises."""
    import jax
    from jax import monitoring

    from repro.async_engine.engine import make_engine
    registered = []
    register = monitoring.register_event_duration_secs_listener

    def spy(callback):
        registered.append(callback)
        register(callback)

    monkeypatch.setattr(monitoring, "register_event_duration_secs_listener",
                        spy)
    make_engine(_round_job(outer_steps=2)).run()
    assert registered == []

    tr = SpanTracer()
    eng = make_engine(_round_job(outer_steps=2), tracer=tr)

    def failing_eval(params, step, time):
        raise RuntimeError("eval failed")

    with pytest.raises(RuntimeError, match="eval failed"):
        eng.run(eval_every=1, eval_fn=failing_eval)
    assert len(registered) == 1
    done = [traced_fp32[1], tr]
    before = [len(t) for t in done]
    jax.jit(lambda x: x * 3.0 + 1.0)(2.0)        # a fresh lowering
    assert [len(t) for t in done] == before


# ---------------------------------------------------------------------------
# Operator console (headless) over the committed golden stream
# ---------------------------------------------------------------------------

def _console_over(lines):
    state = ConsoleState()
    for ln in lines:
        state.add_line(ln)
    return state, render(state, color=False)


def test_console_once_renders_committed_chaos_partition_stream():
    lines = read_complete_lines(GOLDEN_STREAM)
    assert lines, f"missing committed stream {GOLDEN_STREAM}"
    state, out = _console_over(lines)
    assert state.meta is not None and state.meta.scenario == "chaos_partition"
    # every panel the chaos scenario exercises is present — including the
    # cross-process transport + commit-buffer panels the socket-recorded
    # reference stream carries
    for needle in ("HeLoCo operator console", "chaos_partition",
                   "staleness histogram", "cos(D,m)", "per-language loss",
                   "workers", "runtime health", "delivery / chaos",
                   "transport (per worker process)",
                   "commit-buffer flushes"):
        assert needle in out, f"panel {needle!r} missing:\n{out}"
    # the partitioned worker (wid 3, black-holed from t=2.0) shows dead
    assert state.workers[3]["state"] == "dead"
    assert "dead" in out
    # delivery counters from the runtime records made it to the panel
    # (child-side injection: liveness recovery + dedup, not parent drops)
    assert "liveness_deaths" in out and "redelivered_deduped" in out
    # transport records from every worker process — including the
    # partitioned one: obs frames ride the raw control channel, not the
    # fault-injected data path
    assert len(state.transport) >= 2
    assert any(wid == 3 for wid, _pid in state.transport)
    assert state.n_flushes >= 1 and "batch-full" in out
    # a clean committed stream renders no drift footer
    assert "schema drift" not in out
    assert state.decoder.stream_version == schema.SCHEMA_VERSION


def test_console_surfaces_unknown_kind_instead_of_crashing():
    lines = [_meta_line(schema.SCHEMA_VERSION + 1),
             '{"kind": "quantum_flux", "q": 1}']
    state, out = _console_over(lines)
    assert "schema drift" in out and "quantum_flux" in out


def test_console_cli_once_smoke(capsys):
    from repro.obs.console import main as console_main
    assert console_main([GOLDEN_STREAM, "--once"]) == 0
    out = capsys.readouterr().out
    assert "HeLoCo operator console" in out and "chaos_partition" in out


def test_trace_cli_validate(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main
    tr = SpanTracer()
    with tr.span("s"):
        pass
    builds = program_build_listener(tr)
    for fun in ("jit(step)", "jit(wrapped)", "jit(wrapped)"):
        builds(PROGRAM_BUILD_EVENT, 0.01, fun_name=fun)
    builds("/jax/core/compile/backend_compile_duration", 0.5, fun_name="x")
    p = tr.write(str(tmp_path / "t.json"))
    capsys.readouterr()
    assert obs_main(["trace", p, "--validate"]) == 0
    out = capsys.readouterr().out
    assert "program builds by function (3)" in out
    assert [ln.split() for ln in out.splitlines() if "jit(" in ln] == [
        ["jit(wrapped)", "x2"], ["jit(step)", "x1"]]
    bad = tmp_path / "bad.json"
    bad.write_text('{"traceEvents": [{"ph": "X"}]}')
    capsys.readouterr()
    assert obs_main(["trace", str(bad), "--validate"]) == 1


def test_sparkline_shape():
    assert sparkline([]) == ""
    s = sparkline([0, 1, 2, 3], width=4)
    assert len(s) == 4 and s[0] == "▁" and s[-1] == "█"
    assert sparkline([5.0] * 3) == "▁▁▁"        # constant series: no crash


# ---------------------------------------------------------------------------
# Schema v4 forward compatibility: a v3-era reader over a v4 stream
# ---------------------------------------------------------------------------

def test_v3_reader_skips_but_counts_v4_transport_and_flush_records(
        monkeypatch):
    """A PR-7-era (schema v3) StreamDecoder over today's committed v4
    reference stream — which carries `transport` and `flush` records —
    must skip-but-COUNT the new kinds, keep decoding every kind it
    knows, and surface the version gap in the drift report instead of
    silently thinning the stream."""
    monkeypatch.setattr(schema, "SCHEMA_VERSION", 3)
    monkeypatch.setattr(schema, "KINDS", {
        k: v for k, v in schema.KINDS.items()
        if k not in ("transport", "flush")})
    lines = read_complete_lines(GOLDEN_STREAM)
    dec = StreamDecoder()
    decoded = [dec.decode(ln) for ln in lines]
    assert dec.stream_version == 4 and dec.newer_stream
    assert dec.unknown_kinds["transport"] >= 2     # >= 2 worker procs
    assert dec.unknown_kinds["flush"] >= 1
    kinds = {type(r).__name__ for r in decoded if r is not None}
    assert {"RunMeta", "ArrivalMetrics", "EvalMetrics"} <= kinds
    report = "\n".join(dec.drift_report())
    assert "v4 > reader v3" in report
    assert "transport" in report and "flush" in report
    # even a STRICT v3 reader tolerates-and-counts the declared-newer
    # stream (the loud path is reserved for same-version drift)
    strict = StreamDecoder(strict=True)
    for ln in lines:
        strict.decode(ln)
    assert strict.unknown_kinds["transport"] >= 2


# ---------------------------------------------------------------------------
# Aggregation layer + web dashboard
# ---------------------------------------------------------------------------

def test_web_snapshot_contains_acceptance_panels():
    """Acceptance: `python -m repro.obs web --snapshot` over the
    committed reference stream aggregates non-empty arrival-rate,
    staleness, transport, and flush panels."""
    from repro.obs.web import snapshot_panels
    p = snapshot_panels(GOLDEN_STREAM)
    assert p["meta"]["scenario"] == "chaos_partition"
    assert p["meta"]["schema_version"] == schema.SCHEMA_VERSION
    assert p["arrivals"]["commits"] > 0
    assert p["arrivals"]["rate_per_sec"] > 0
    assert p["staleness"]
    assert (sum(p["staleness"].values())
            == p["arrivals"]["commits"])
    # cross-process transport panel: per-(wid/pid) rows + summed totals
    assert len(p["transport"]["workers"]) >= 2
    assert p["transport"]["totals"]["frames_sent"] > 0
    assert p["transport"]["totals"]["compute_s"] > 0
    # commit-buffer flush panel
    assert p["flush"]["flushes"] >= 1
    assert "batch-full" in p["flush"]["reasons"]
    assert p["flush"]["fused"] + p["flush"]["sequential"] >= 2
    # a clean committed stream aggregates drift-free
    assert p["drift"] == []


def test_web_snapshot_cli(capsys):
    from repro.obs.__main__ import main as obs_main
    assert obs_main(["web", GOLDEN_STREAM, "--snapshot"]) == 0
    p = json.loads(capsys.readouterr().out)
    for panel in ("arrivals", "staleness", "transport", "flush"):
        assert p[panel], f"panel {panel!r} empty in --snapshot output"


def test_console_and_web_share_one_aggregation_code_path():
    """The satellite contract: console, web, and snapshot all read ONE
    rollup (repro.obs.metrics.MetricsAggregator) — same stream in,
    identical panels out."""
    from repro.obs.web import snapshot_panels
    state = ConsoleState()
    for ln in read_complete_lines(GOLDEN_STREAM):
        state.add_line(ln)
    assert state.panels() == snapshot_panels(GOLDEN_STREAM)


def test_web_server_routes_live(tmp_path):
    """The dashboard server end-to-end on an ephemeral port: / serves
    the self-contained page, /snapshot.json tracks a growing stream
    through the tail hub, /events pushes SSE frames, unknown paths 404."""
    import urllib.error
    import urllib.request

    from repro.obs import web

    lines = read_complete_lines(GOLDEN_STREAM)
    stream = tmp_path / "live.jsonl"
    stream.write_text("\n".join(lines[:3]) + "\n")
    hub = web._Hub(str(stream), poll=0.02)
    hub.start()
    handler = type("H", (web._Handler,),
                   {"hub": hub, "sse_interval": 0.05})
    httpd = web.ThreadingHTTPServer(("127.0.0.1", 0), handler)
    httpd.daemon_threads = True
    t = threading.Thread(target=httpd.serve_forever,
                         kwargs={"poll_interval": 0.05}, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        page = urllib.request.urlopen(base + "/", timeout=10).read()
        assert b"HeLoCo dashboard" in page and b"EventSource" in page
        # grow the stream; the hub tails the rest into the aggregate
        with open(stream, "a") as f:
            f.write("\n".join(lines[3:]) + "\n")
        snap = {}
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            snap = json.loads(urllib.request.urlopen(
                base + "/snapshot.json", timeout=10).read())
            if snap.get("transport") and snap.get("flush"):
                break
            time.sleep(0.05)
        assert snap["arrivals"]["commits"] > 0
        assert snap["transport"] and snap["flush"]
        # one SSE data frame arrives (skipping keepalive comments)
        resp = urllib.request.urlopen(base + "/events", timeout=10)
        payload = None
        for _ in range(100):
            ln = resp.readline()
            if ln.startswith(b"data: "):
                payload = json.loads(ln[6:])
                break
        resp.close()
        assert payload is not None and payload["arrivals"]["commits"] > 0
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(base + "/nope", timeout=10)
        assert exc.value.code == 404
    finally:
        httpd.shutdown()
        httpd.server_close()
        hub.stop()


# ---------------------------------------------------------------------------
# Commit-buffer flush telemetry (schema v4 "flush" records)
# ---------------------------------------------------------------------------

def _tiny_cfg(commit_batch=2, outer_steps=6, inner_steps=1,
              compression="none"):
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.configs.base import InnerOptConfig, OuterOptConfig, RunConfig
    cfg = reduced(get_config("tinygpt-15m"))
    return dataclasses.replace(RunConfig(
        model=cfg, n_workers=2, inner_steps=inner_steps,
        outer_steps=outer_steps,
        batch_size=2, seq_len=16, worker_paces=(1.0, 2.0), non_iid=True,
        inner=InnerOptConfig(lr=3e-3, warmup_steps=2, total_steps=100),
        outer=OuterOptConfig(method="heloco", compression=compression)),
        commit_batch=commit_batch)


def test_flush_records_emitted_from_commit_buffer():
    """PR 9's batching is no longer a black box: every multi-arrival
    flush of the server commit buffer lands in the stream as one "flush"
    record carrying depth, reason, and the fused-vs-sequential split."""
    from repro.async_engine.engine import make_engine
    rec = TelemetryRecorder()
    eng = make_engine(_tiny_cfg(commit_batch=2, outer_steps=6),
                      telemetry=rec)
    eng.run(eval_every=3)
    fl = rec.flush_records()
    assert fl, "commit_batch=2 run produced no flush records"
    assert all(f.depth >= 2 for f in fl)          # singles skip the buffer
    assert {f.reason for f in fl} <= {"batch-full", "eval", "ckpt", "close"}
    assert "batch-full" in {f.reason for f in fl}
    # fused + sequential always account for the whole buffered depth
    assert all(f.fused + f.sequential == f.depth for f in fl)
    # ... and the server's cumulative totals agree (the stats_summary /
    # console "commit-buffer flushes" panel reads these)
    assert eng.server.flush_totals["flushes"] == len(fl)
    assert eng.server.flush_totals["depth_max"] == max(f.depth for f in fl)


@pytest.mark.wallclock
def test_free_mode_coalesces_commits_without_losing_arrivals():
    """The free-running loop's opportunistic batch drain (commit_batch>1)
    must conserve arrivals exactly: every commit is recorded once,
    batched or not, and the run still reaches the outer-step target."""
    from repro.async_engine.engine import make_engine, make_eval_fn
    from repro.scenarios import get_scenario
    scn = get_scenario("wallclock_free").overridden(commit_batch=3)
    rec = TelemetryRecorder()
    eng = make_engine(scn, telemetry=rec)
    hist = eng.run(eval_every=scn.eval_cadence,
                   eval_fn=make_eval_fn(eng, batch=scn.eval_batch))
    assert len(hist.arrivals) == scn.outer_steps
    assert eng.stats["arrivals"] == len(hist.arrivals)
    assert len(rec.arrivals()) == len(hist.arrivals)
    for f in rec.flush_records():                 # coalescing opportunistic
        assert 2 <= f.depth <= 3
        assert f.reason in {"batch-full", "eval", "ckpt", "close"}


# ---------------------------------------------------------------------------
# Single-writer sink enforcement (TailReader multi-writer satellite)
# ---------------------------------------------------------------------------

def test_second_recorder_on_same_sink_rejected_loudly(tmp_path):
    """Interleaved flushes from two writers can tear JSONL lines in ways
    no tail reader can repair — the recorder enforces one live writer
    per sink via an exclusive flock held for its lifetime."""
    sink = str(tmp_path / "s.jsonl")
    rec = TelemetryRecorder(sink=sink)
    rec.record_arrival(_fake_arrival(0))
    with pytest.raises(RuntimeError, match="live writer"):
        TelemetryRecorder(sink=sink)
    # the rejected opener never clobbered the live writer's bytes
    assert read_complete_lines(sink)
    rec.close()
    # close releases the lock: the sink is reusable afterwards
    rec2 = TelemetryRecorder(sink=sink)
    rec2.close()


# ---------------------------------------------------------------------------
# The byte-identity contract: observability on == golden off
# ---------------------------------------------------------------------------

def test_golden_identical_with_telemetry_tracing_and_runtime_records(
        tmp_path):
    """Running a golden scenario with the FULL observability stack on —
    live-sink telemetry, span tracing, periodic runtime records — must
    reproduce the committed golden trace byte-for-byte (observation
    never perturbs the run), while actually producing a live stream,
    runtime records, and a valid Chrome trace."""
    from repro.async_engine.engine import make_engine, make_eval_fn
    from repro.scenarios import get_scenario, trace

    scn = get_scenario("paper_hetero_severe")
    sink = str(tmp_path / "live.jsonl")
    rec = TelemetryRecorder(sink=sink)
    tr = SpanTracer()
    eng = make_engine(scn, telemetry=rec, tracer=tr, runtime_record_every=2)
    hist = eng.run(eval_every=scn.eval_cadence,
                   eval_fn=make_eval_fn(eng, batch=scn.eval_batch))
    rec.close()

    arrivals = [[a["outer_step"], a["worker_id"],
                 a["outer_step"] - 1 - a["staleness"], a["staleness"],
                 a["lang"], a["rho"], a["sim_time"], bool(a["dropped"])]
                for a in hist.arrivals]
    doc = {
        "schema": trace.SCHEMA_VERSION,
        "scenario": scn.to_dict(),
        "engine": scn.engine, "mode": scn.mode, "exact": scn.exact,
        "arrivals": arrivals, "evals": hist.evals,
        "tokens": int(hist.tokens), "comm_bytes": int(hist.comm_bytes),
        "final_time": float(hist.final_time),
        "param_digest": trace.param_digest(eng.server.state.params),
        "param_fingerprint": trace.param_fingerprint(
            eng.server.state.params),
    }
    res = trace.verify(scn, fresh=doc)
    assert res.ok, res.report()

    # the observability artifacts actually materialized
    assert rec.runtime_records(), "no runtime records at cadence 2"
    rt = rec.runtime_records()[-1]
    assert rt.workers_total == scn.n_workers
    assert len(tr) > 0 and validate_chrome_trace(tr.to_chrome()) == []
    dec = StreamDecoder(strict=True)
    for ln in read_complete_lines(sink):
        dec.decode(ln)
    assert dec.meta is not None and not dec.drift_report()
    kinds = {type(r).__name__ for r in map(dec.decode,
                                           read_complete_lines(sink))
             if r is not None}
    assert {"ArrivalMetrics", "EvalMetrics", "RuntimeMetrics"} <= kinds
