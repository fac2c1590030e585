"""The readers of the program's spans inside the inner round and of its
program-build counter: host milliseconds (or builds) per ``worker_round``
in the window, nothing without a round or from a program that does not
record the span, and one reader for a metric and its ``.int8`` twin."""
import json
import os

import pytest

from chipbench import harness
from chipbench.cells import HERE, ROOT, load_cell
from chipbench.trace import CLOSE, OPEN, TracedRun

IN_ROUND = ("batch_sample", "batch_to_device", "inner_dispatch",
            "round_copy", "pseudo_gradient")
SPAN_METRICS = {name + "_ms": name for name in IN_ROUND + ("round_dispatch",)}
METRICS = sorted(SPAN_METRICS) + ["builds_per_round"]


def _run(spans):
    return TracedRun.from_events(
        devices=[], host=[(OPEN, 0, 0), (CLOSE, 10, 10)], n_chips=1,
        cell=None, spans=list(spans), peaks={}, tokens=0)


def _round(t, h=2, builds=0, dispatch_ms=1.0):
    """One round of the program from ``t`` (seconds): a dispatch, then a
    ``worker_round`` with its copy, H steps and pseudo-gradient, and the
    given number of program builds. Every child span lasts 1 ms per
    step of its kind, the copy 2 ms, the pseudo-gradient 3 ms."""
    ms = 1e-3
    out = [("round_dispatch", t, t + dispatch_ms * ms, 1),
           ("round_copy", t + 0.01, t + 0.01 + 2 * ms, 1)]
    for i in range(h):
        s = t + 0.02 + 0.01 * i
        out += [("batch_sample", s, s + ms, 1),
                ("batch_to_device", s + 0.002, s + 0.003, 1),
                ("inner_dispatch", s + 0.004, s + 0.005, 1)]
    out += [("program_build", t + 0.05, t + 0.05, 1)] * builds
    out += [("pseudo_gradient", t + 0.06, t + 0.06 + 3 * ms, 1),
            ("worker_round", t + 0.01, t + 0.07, 1),
            ("compress_roundtrip", t + 0.07, t + 0.08, 1),
            ("server_commit", t + 0.08, t + 0.09, 1)]
    return out


def _read(name, spans):
    return harness._metric_reader(name)(_run(spans))


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_the_value_per_round(metric):
    # two rounds of H=2, then a round of H=3 dispatched in 4 ms
    spans = (_round(0.0, builds=3) + _round(1.0, builds=0)
             + _round(2.0, h=3, builds=3, dispatch_ms=4.0))
    want = {"batch_sample_ms": 7 / 3, "batch_to_device_ms": 7 / 3,
            "inner_dispatch_ms": 7 / 3, "round_copy_ms": 2.0,
            "pseudo_gradient_ms": 3.0, "round_dispatch_ms": 2.0,
            "builds_per_round": 2.0}
    assert _read(metric, spans) == pytest.approx(want[metric])


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_without_a_round(metric):
    spans = [s for s in _round(0.0, builds=3) if s[0] != "worker_round"]
    assert _read(metric, spans) is None
    assert _read(metric, []) is None


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_for_a_program_without_the_spans(metric):
    """The parent program records ``worker_round`` and the commit but no
    span inside the round and no builds: nothing is read, not 0."""
    spans = [s for s in _round(0.0) + _round(1.0)
             if s[0] in ("worker_round", "compress_roundtrip",
                         "server_commit")]
    assert _read(metric, spans) is None


def test_no_builds_in_the_window_read_zero():
    assert _read("builds_per_round", _round(0.0) + _round(1.0)) == 0.0


@pytest.mark.parametrize("metric", METRICS)
def test_int8_twin_resolves_to_the_same_reader(metric):
    assert not os.path.exists(os.path.join(HERE, "metrics",
                                           metric + ".int8.py"))
    spans = _round(0.0, builds=3) + _round(1.0, h=3, dispatch_ms=2.0)
    assert harness.quantity(metric + ".int8") == metric
    assert _read(metric + ".int8", spans) == _read(metric, spans)


def test_metrics_are_declared_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for metric in METRICS:
        for name, moves in ((metric, "tokens_per_s"),
                            (metric + ".int8", "tokens_per_s.int8")):
            m = declared[name]
            assert m["moves"] == moves
            assert m["source"] == ("program_counter"
                                   if metric == "builds_per_round"
                                   else "program_span")
    for cell, suffix in (("tinygpt15m.paper-k1", ""),
                         ("gpt2-124m.paper-k1", ""),
                         ("tinygpt15m.w16-k4-int8", ".int8")):
        names = {m["name"] for m in load_cell(cell).per_layer}
        assert {m + suffix for m in METRICS} <= names
