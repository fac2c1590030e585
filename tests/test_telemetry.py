"""Telemetry subsystem: kernel-fused update-quality stats vs the
per-leaf reference (property-based, every registered method, stacked
axes, int8 path), schema round-trips, the byte-identity contract of the
telemetry-on arrival path, and budget accounting in the sim engine."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import HeLoCoConfig, OuterOptConfig
from repro.core import methods as M
from repro.core import packing
from repro.core.compression import roundtrip_with_error_feedback
from repro.core.heloco import apply_arrival_packed
from repro.async_engine.engine import Budget, make_engine
from repro.async_engine.server import Synchronizer
from repro.scenarios import registry, trace
from repro.telemetry import (
    ArrivalMetrics, TelemetryRecorder, from_json_line, reference_moments,
    staleness_alignment, stats_from_moments, to_json_line,
)

H = HeLoCoConfig()


def _rand_tree(seed: int):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(1, 4))
    shapes = {
        "stack": (k, int(rng.integers(1, 5)), int(rng.integers(1, 7))),
        "mat": (int(rng.integers(1, 9)), int(rng.integers(1, 9))),
        "vec": (int(rng.integers(1, 150)),),
    }
    stacked = {"stack": 1, "mat": 0, "vec": 0}
    key = jax.random.PRNGKey(seed)
    tree = {n: jax.random.normal(jax.random.fold_in(key, i), s)
            for i, (n, s) in enumerate(sorted(shapes.items()))}
    return tree, stacked


def _moments_close(got, want, rtol=1e-3, atol=1e-3):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Kernel-side stats == per-leaf reference (the core telemetry contract)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.floats(0.0, 12.0, allow_nan=False))
def test_fused_stats_match_reference_every_method(seed, tau):
    """The (R, 4) moments the fused sweep emits reduce to exactly the
    per-leaf reference moments — for EVERY registered method, over
    random shapes and stacked layer axes."""
    params, stacked = _rand_tree(seed % 10_000)
    delta = {k: -0.4 * v + 0.05 for k, v in params.items()}
    mom = {k: 0.3 * v - 0.02 for k, v in params.items()}
    layout = packing.build_layout(params, stacked)
    pbuf = packing.pack(layout, params)
    mbuf = packing.pack(layout, mom)
    tau_j = jnp.asarray(tau, jnp.float32)
    for m in M.all_methods():
        abuf = packing.zeros(layout) if m.uses_buffer else None
        out = apply_arrival_packed(pbuf, mbuf, delta, layout,
                                   method=m.name, outer_lr=0.7, mu=0.9,
                                   h=H, rho=0.447, tau=tau, abuf=abuf,
                                   phase=1, with_stats=True)
        got = jnp.sum(out[-1], axis=0)
        ctx = M.ArrivalCtx(outer_lr=0.7, mu=0.9, h=H, rho=0.447,
                           tau=tau_j, phase=1, stacked_axes=stacked)
        corrected = m.correct(m, ctx, delta, mom)
        want = reference_moments(delta, mom, corrected)
        _moments_close(got, want)


def test_fused_stats_int8_packed_delta():
    """The int8 compression path hands the synchronizer a Packed decoded
    buffer; the fused stats must match the reference computed on the
    decoded pytree."""
    params, stacked = _rand_tree(7)
    delta = {k: 0.03 * v for k, v in params.items()}
    mom = {k: -0.2 * v for k, v in params.items()}
    layout = packing.build_layout(params, stacked)
    decoded, _, _ = roundtrip_with_error_feedback(delta, None, "int8",
                                                  layout=layout)
    assert isinstance(decoded, packing.Packed)
    pbuf = packing.pack(layout, params)
    mbuf = packing.pack(layout, mom)
    out = apply_arrival_packed(pbuf, mbuf, decoded, layout,
                               method="heloco", outer_lr=0.7, mu=0.9, h=H,
                               with_stats=True)
    got = jnp.sum(out[-1], axis=0)
    decoded_tree = packing.unpack(layout, decoded.buf, jnp.float32)
    ctx = M.ArrivalCtx(outer_lr=0.7, mu=0.9, h=H, stacked_axes=stacked)
    m = M.get("heloco")
    want = reference_moments(decoded_tree, mom,
                             m.correct(m, ctx, decoded_tree, mom))
    _moments_close(got, want)


def test_stats_from_moments_math():
    s = stats_from_moments([2.0, 4.0, 1.0, 9.0])
    assert s.delta_norm == 2.0 and s.momentum_norm == 1.0
    np.testing.assert_allclose(s.cos_align, 2.0 / (2.0 * 1.0))
    np.testing.assert_allclose(s.corrected_frac, 3.0 / 2.0)
    z = stats_from_moments([0.0, 0.0, 4.0, 0.0])   # dropped arrival shape
    assert z.cos_align == 0.0 and z.corrected_frac == 0.0
    assert z.delta_norm == 0.0 and z.momentum_norm == 2.0


# ---------------------------------------------------------------------------
# Synchronizer integration: packed vs reference engines agree
# ---------------------------------------------------------------------------

def _feed(sv, n=6, stale_by=3):
    params = sv.state.params
    for i in range(n):
        delta = jax.tree.map(
            lambda x: 0.05 * jax.random.normal(
                jax.random.PRNGKey(i), x.shape), params)
        sv.on_arrival(delta, s_i=max(0, sv.t - stale_by), worker_id=0)


def test_synchronizer_stats_packed_matches_reference_path():
    params = {"a": jax.random.normal(jax.random.PRNGKey(0), (24, 10)),
              "b": jax.random.normal(jax.random.PRNGKey(1), (131,))}
    cfg = OuterOptConfig(method="heloco")
    svA = Synchronizer(jax.tree.map(jnp.copy, params), cfg, 3,
                       packed=True, telemetry=True)
    svB = Synchronizer(jax.tree.map(jnp.copy, params), cfg, 3,
                       packed=False, telemetry=True)
    _feed(svA)
    _feed(svB)
    for ra, rb in zip(svA.records, svB.records):
        assert ra.cos_align is not None and rb.cos_align is not None
        np.testing.assert_allclose(ra.cos_align, rb.cos_align,
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(ra.corrected_frac, rb.corrected_frac,
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(ra.delta_norm, rb.delta_norm,
                                   rtol=1e-3, atol=1e-3)
    # stats off by default: no diagnostics attached
    svC = Synchronizer(jax.tree.map(jnp.copy, params), cfg, 3)
    _feed(svC, n=2)
    assert all(r.cos_align is None for r in svC.records)


def test_dropped_arrival_stats_are_momentum_only():
    params = {"w": jax.random.normal(jax.random.PRNGKey(2), (40,))}
    cfg = OuterOptConfig(method="heloco", drop_stale_after=1)
    sv = Synchronizer(params, cfg, 2, telemetry=True)
    _feed(sv, n=6, stale_by=4)
    dropped = [r for r in sv.records if r.dropped]
    assert dropped
    for r in dropped:
        assert r.cos_align == 0.0 and r.delta_norm == 0.0
        assert r.momentum_norm > 0.0


# ---------------------------------------------------------------------------
# Schema + recorder round-trip
# ---------------------------------------------------------------------------

def test_schema_roundtrip_and_drift_rejection(tmp_path):
    a = ArrivalMetrics(outer_step=3, worker_id=1, staleness=2, rho=0.5,
                       sim_time=6.0, wall_time=0.1, lang="de",
                       dropped=False, cos_align=0.25, corrected_frac=0.1,
                       delta_norm=1.5, momentum_norm=0.7,
                       mixture=(0.8, 0.2), tokens_total=640)
    assert from_json_line(to_json_line(a)) == a
    with pytest.raises(ValueError):
        from_json_line('{"kind": "arrival", "outer_step": 1, "nope": 2}')
    with pytest.raises(ValueError):
        from_json_line('{"kind": "wat"}')


def test_staleness_alignment_analysis():
    def arr(tau, cos, dropped=False):
        return ArrivalMetrics(outer_step=0, worker_id=0, staleness=tau,
                              rho=1.0, sim_time=0.0, wall_time=0.0,
                              lang="", dropped=dropped, cos_align=cos,
                              corrected_frac=0.1, delta_norm=1.0,
                              momentum_norm=1.0)
    curve = staleness_alignment([arr(0, 0.8), arr(0, 0.6), arr(3, 0.1),
                                 arr(5, -0.2, dropped=True)])
    assert [pt["staleness"] for pt in curve] == [0, 3]
    np.testing.assert_allclose(curve[0]["mean_cos_align"], 0.7)
    assert curve[0]["n"] == 2


# ---------------------------------------------------------------------------
# The acceptance contract: telemetry-on runs are byte-identical
# ---------------------------------------------------------------------------

def test_telemetry_on_arrival_path_is_byte_identical_to_golden():
    """Running a registered scenario WITH telemetry must reproduce its
    committed golden trace exactly (param digest included) — the stats
    are extra kernel outputs, never extra math in the update."""
    scn = registry.get_scenario("paper_hetero_severe")
    rec = TelemetryRecorder()
    doc = trace.run_trace(scn, telemetry=rec)
    res = trace.verify(scn, fresh=doc)
    assert res.ok, res.failures
    arrivals = rec.arrivals()
    assert len(arrivals) == scn.outer_steps
    assert all(a.cos_align is not None for a in arrivals)
    assert rec.evals() and rec.evals()[-1].per_lang
    assert rec.meta is not None and rec.meta.scenario == scn.name


# ---------------------------------------------------------------------------
# Budget accounting (sim engine; the wallclock lane covers the runtime)
# ---------------------------------------------------------------------------

TINY = registry.get_scenario("paper_hetero_severe")
ROUND_TOKENS = TINY.inner_steps * TINY.batch_size * TINY.seq_len


def test_budget_validation():
    with pytest.raises(AssertionError):
        Budget("nope", 10)
    with pytest.raises(AssertionError):
        Budget("fixed_tokens", 0)
    b = Budget("fixed_tokens", 100)
    assert b.over_tokens(100) and not b.over_tokens(99)
    assert not b.over_time(1e9)
    w = Budget("fixed_wallclock", 5.0)
    assert w.over_time(5.01) and not w.over_time(5.0)
    assert not w.over_tokens(10 ** 12)


def test_fixed_tokens_stops_within_one_round_sim():
    target = ROUND_TOKENS * 5
    eng = make_engine(TINY.materialize().run_cfg)
    hist = eng.run(budget=Budget("fixed_tokens", target))
    assert target <= hist.tokens < target + ROUND_TOKENS
    assert len(hist.arrivals) < TINY.outer_steps


def test_fixed_wallclock_never_commits_past_horizon_sim():
    horizon = 8.0
    eng = make_engine(TINY.materialize().run_cfg)
    hist = eng.run(budget=Budget("fixed_wallclock", horizon))
    assert hist.arrivals and len(hist.arrivals) < TINY.outer_steps
    assert all(a["sim_time"] <= horizon for a in hist.arrivals)
    assert hist.final_time <= horizon
    # and the run would have continued: the NEXT arrival of an unbudgeted
    # replay lands past the horizon
    full = make_engine(TINY.materialize().run_cfg).run()
    nxt = [a["sim_time"] for a in full.arrivals
           if a["sim_time"] > horizon]
    assert nxt, "horizon not binding for this scenario"


def test_fixed_tokens_stops_sync_engine_within_one_round():
    scn = registry.get_scenario("sync_baseline")
    rc = scn.materialize().run_cfg
    round_tokens = scn.n_workers * scn.inner_steps * scn.batch_size \
        * scn.seq_len
    target = round_tokens * 2
    hist = make_engine(rc).run(budget=Budget("fixed_tokens", target))
    assert target <= hist.tokens < target + round_tokens


def test_fixed_wallclock_stops_sync_engine_before_horizon():
    scn = registry.get_scenario("sync_baseline")
    rc = scn.materialize().run_cfg
    # slowest worker pace 6.0 x 2 inner steps = 12s per barrier round
    hist = make_engine(rc).run(budget=Budget("fixed_wallclock", 30.0))
    assert hist.final_time <= 30.0
    assert 0 < len(hist.arrivals) < scn.outer_steps


@pytest.mark.wallclock
def test_budget_accounting_wallclock_engine():
    """Both budget kinds stop the deterministic ConcurrentRuntime within
    one outer round, same semantics as the simulator."""
    m = TINY.materialize()
    target = ROUND_TOKENS * 4
    eng = make_engine(m.run_cfg, "wallclock", mode="deterministic")
    hist = eng.run(budget=Budget("fixed_tokens", target))
    assert target <= hist.tokens < target + ROUND_TOKENS

    eng2 = make_engine(m.run_cfg, "wallclock", mode="deterministic")
    hist2 = eng2.run(budget=Budget("fixed_wallclock", 8.0))
    assert hist2.arrivals and all(a["sim_time"] <= 8.0
                                  for a in hist2.arrivals)


@pytest.mark.wallclock
def test_telemetry_streams_from_wallclock_engine():
    rec = TelemetryRecorder()
    m = TINY.materialize()
    eng = make_engine(m.run_cfg, "wallclock", mode="deterministic",
                      telemetry=rec)
    hist = eng.run()
    arrivals = rec.arrivals()
    assert len(arrivals) == len(hist.arrivals)
    assert all(a.cos_align is not None for a in arrivals)
    assert math.isfinite(sum(a.wall_time for a in arrivals))
