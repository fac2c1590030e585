"""The trace reduction on a small synthetic device trace: busy and idle
time, program time per role, kernel time inside a program, and the
attribution of idle gaps to the host span active at the time."""
import pytest

from chipbench import trace
from chipbench.trace import OPEN, CLOSE, Device, TracedRun


def _run(devices, host, spans=(), tokens=0, n_chips=None):
    return TracedRun.from_events(
        devices=devices, host=host, n_chips=n_chips or len(devices),
        cell=None, spans=list(spans), peaks={}, tokens=tokens)


def _chip():
    modules = [("jit_step(1)", 100, 400), ("jit__apply(2)", 500, 600),
               ("jit_step(1)", 700, 900), ("jit_copy(3)", 1500, 1600)]
    ops = [("%fusion.1 = f32[8] fusion(...)", 100, 300),
           ("%fusion.2 = f32[8] fusion(...)", 250, 400),   # overlaps
           ("%_apply.2 = f32[8,3] custom-call(f32[8,128] %x)", 500, 540),
           ("%_apply.3 = (f32[8,128]) custom-call(f32[8,128] %y)", 550, 600),
           ("%fusion.3 = f32[8] fusion(...)", 700, 900),
           ("%copy.1 = f32[8] copy(f32[8] %z)", 1500, 1600)]
    return Device(modules, ops)


HOST = [(OPEN, 0, 0), (CLOSE, 2000, 2000),
        ("worker_round", 0, 480), ("server_commit", 480, 650),
        ("eval", 1000, 1450), ("worker_round", 1450, 2000)]


def test_busy_time_of_overlapping_intervals():
    busy = trace.Busy([(30, 40), (0, 10), (5, 20), (35, 36)])
    assert busy.within(0, 100) == 30
    assert busy.within(8, 32) == 14
    assert busy.within(20, 30) == 0
    assert trace.Busy([]).within(3, 7) == 0


def test_idle_share_and_busy_seconds():
    run = _run([_chip()], HOST)
    # busy: 100-400, 500-540, 550-600, 700-900, 1500-1600 = 300+40+50+200+100
    assert run.busy_s() == pytest.approx(690e-9)
    assert run.window_s == pytest.approx(2000e-9)


def test_busy_is_averaged_over_chips():
    quiet = Device([("jit_step(1)", 0, 1000)], [("%f = f32[1] f()", 0, 1000)])
    run = _run([_chip(), quiet], HOST)
    assert run.busy_s() == pytest.approx((690e-9 + 1000e-9) / 2)


def test_program_and_kernel_time_by_role():
    run = _run([_chip()], HOST)
    assert run.module_seconds("inner_step") == (pytest.approx(500e-9), 2)
    assert run.module_seconds("commit") == (pytest.approx(100e-9), 1)
    # only the custom calls inside the commit program count as its kernels
    assert run.kernel_seconds("commit_kernels") == (pytest.approx(90e-9), 2)


def test_events_outside_the_window_are_left_out():
    host = [(OPEN, 450, 450), (CLOSE, 1000, 1000)]
    run = _run([_chip()], host)
    assert run.module_seconds("inner_step") == (pytest.approx(200e-9), 1)


def test_idle_gaps_are_attributed_to_the_host_span():
    run = _run([_chip()], HOST)
    b = run.breakdown()
    idle = dict(b["idle_gaps"])
    # idle: 0-100 and 400-480 in worker_round, 480-500, 540-550 and 600-650
    # in the commit, 650-700 and 900-1000 in no span, 1000-1450 in eval,
    # 1450-1500 and 1600-2000 in worker_round
    assert idle["eval"] == pytest.approx(450e-9)
    assert idle["worker_round"] == pytest.approx((100 + 80 + 50 + 400) * 1e-9)
    assert idle["server_commit"] == pytest.approx((20 + 10 + 50) * 1e-9)
    assert idle["no program span"] == pytest.approx((50 + 100) * 1e-9)
    assert sum(idle.values()) == pytest.approx(2000e-9 - run.busy_s())
    ops = dict(b["device_ops"])
    assert ops == {"jit_step": pytest.approx(500e-9),
                   "jit__apply": pytest.approx(100e-9),
                   "jit_copy": pytest.approx(100e-9)}


def test_host_spans_and_arrivals():
    spans = [("worker_round", 0.0, 0.010, 1), ("server_commit", 0.01, 0.02, 1),
             ("server_commit_batch", 0.02, 0.03, 4), ("eval", 0.03, 0.05, 1)]
    run = _run([_chip()], HOST, spans=spans)
    assert run.span_ms("worker_round") == [pytest.approx(10.0)]
    assert run.arrivals() == [1, 4]


def test_a_trace_without_window_markers_is_refused():
    with pytest.raises(ValueError):
        _run([_chip()], [("eval", 0, 10)])
