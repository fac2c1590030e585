"""Batched-arrival fast path (docs/scale.md): the K-stacked multi-apply
property-tested against K sequential applications for EVERY registered
outer method (random K / shapes / stacked axes / int8-quantized deltas,
telemetry moments against the per-leaf reference), the commit-buffer
semantics (K=1 byte-identity, idempotent redelivery, drop interleaving),
the event-queue compaction guarantee under a crash/rejoin storm at
N=1k, the history ring, and the hogwild batch-ramp-up accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs.base import HeLoCoConfig, OuterOptConfig
from repro.core import compression, methods as M, packing
from repro.core.heloco import (
    apply_arrival, apply_arrivals_packed, init_outer_state,
)
from repro.async_engine.engine import (
    HISTORY_WINDOW, EventQueue, History, WorkerArena,
)
from repro.async_engine.server import Synchronizer
from repro.telemetry.stats import reference_moments_multi

H = HeLoCoConfig()


def _tree(seed: int, stacked: bool):
    """Small mixed-shape param tree; optionally one scan-stacked leaf
    (stacked_axes=1) so the layout's per-slice blocks are exercised."""
    key = jax.random.PRNGKey(seed)
    shapes = {"w": (19, 7), "b": (133,), "s": (3, 5, 9)}
    tree = {k: jax.random.normal(jax.random.fold_in(key, i), s)
            for i, (k, s) in enumerate(shapes.items())}
    axes = {"w": 0, "b": 0, "s": 1 if stacked else 0}
    return tree, axes


def _deltas(seed: int, k: int, stacked: bool, int8: bool):
    out = []
    for j in range(k):
        d, _ = _tree(1000 + seed * 31 + j, stacked)
        d = jax.tree.map(lambda x: 0.05 * x, d)
        if int8:
            # what the server sees after the engine decodes the wire form
            d = compression.decompress(compression.compress(d, "int8"), d)
        out.append(d)
    return out


# ---------------------------------------------------------------------------
# Property: batched K-apply == K sequential applies, every method
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=2, max_value=5),
       st.integers(min_value=0, max_value=3),
       st.integers(min_value=0, max_value=1),
       st.integers(min_value=0, max_value=1))
def test_multi_apply_matches_sequential_every_method(k, seed, stacked_i,
                                                     int8_i):
    stacked, int8 = bool(stacked_i), bool(int8_i)
    params, axes = _tree(seed, stacked)
    deltas = _deltas(seed, k, stacked, int8)
    layout = packing.build_layout(params, axes)
    rhos = [1.0 / np.sqrt(1.0 + (j % 3)) for j in range(k)]
    taus = [float(j % 3) for j in range(k)]
    for m in M.all_methods():
        phases = list(range(2, 2 + k)) if m.uses_buffer else [None] * k
        # per-leaf sequential reference (the paper-exact path)
        state = init_outer_state(params, with_aux=m.uses_buffer)
        for j in range(k):
            state = apply_arrival(state, deltas[j], method=m,
                                  outer_lr=0.7, mu=0.9, h=H, rho=rhos[j],
                                  tau=taus[j], stacked_axes=axes,
                                  phase=phases[j])
        ref_mom = reference_moments_multi(
            init_outer_state(params, with_aux=m.uses_buffer), deltas,
            method=m, outer_lr=0.7, mu=0.9, h=H, rhos=rhos, taus=taus,
            phases=phases if m.uses_buffer else None, stacked_axes=axes)
        # one fused multi-apply on the packed buffers
        pbuf = packing.pack(layout, params)
        mbuf = packing.zeros(layout)
        out = apply_arrivals_packed(
            pbuf, mbuf, deltas, layout, method=m, outer_lr=0.7, mu=0.9,
            h=H, rhos=rhos, taus=taus,
            abuf=packing.zeros(layout) if m.uses_buffer else None,
            phases=phases if m.uses_buffer else None, with_stats=True)
        if m.uses_buffer:
            p2, m2, a2, stats = out
            ref_aux = packing.pack(layout, state.aux)
            np.testing.assert_allclose(np.asarray(a2), np.asarray(ref_aux),
                                       atol=5e-6, rtol=1e-5,
                                       err_msg=f"{m.name} aux K={k}")
        else:
            p2, m2, stats = out
        got_p = packing.unpack(layout, p2)
        got_m = packing.unpack(layout, m2)
        for a, b in zip(jax.tree.leaves(got_p),
                        jax.tree.leaves(state.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-6, rtol=1e-5,
                                       err_msg=f"{m.name} params K={k}")
        for a, b in zip(jax.tree.leaves(got_m),
                        jax.tree.leaves(state.momentum)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-6, rtol=1e-5,
                                       err_msg=f"{m.name} momentum K={k}")
        # (K, R, 4) kernel moments reduce to the (K, 4) per-leaf reference
        assert stats.shape[0] == k and stats.shape[-1] == 4
        np.testing.assert_allclose(np.asarray(jnp.sum(stats, axis=1)),
                                   np.asarray(ref_mom),
                                   atol=1e-3, rtol=1e-3,
                                   err_msg=f"{m.name} moments K={k}")


# ---------------------------------------------------------------------------
# Commit buffer semantics on the Synchronizer
# ---------------------------------------------------------------------------

def _params(d: int = 1024, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    return {f"b{i}": jax.random.normal(jax.random.fold_in(key, i), (d // 4,))
            for i in range(4)}


def _delta_list(n: int, d: int = 1024):
    key = jax.random.PRNGKey(7)
    return [jax.tree.map(
        lambda x: 0.01 * x,
        {f"b{i}": jax.random.normal(jax.random.fold_in(key, 10 * j + i),
                                    (d // 4,))
         for i in range(4)}) for j in range(n)]


def test_commit_batch_one_is_byte_identical():
    cfg = OuterOptConfig(method="heloco", delay_weighting=True)
    deltas = _delta_list(5)
    a = Synchronizer(_params(), cfg, n_workers=4, telemetry=True)
    b = Synchronizer(_params(), cfg, n_workers=4, telemetry=True,
                     commit_batch=1)
    recs_a, recs_b = [], []
    for i, d in enumerate(deltas):
        recs_a.append(a.on_arrival(d, max(0, a.t - 2), i % 4))
        out = b.buffer_arrival(d, max(0, b.t - 2), i % 4)
        assert out is not None and len(out) == 1   # K=1 flushes eagerly
        recs_b.extend(out)
    for x, y in zip(jax.tree.leaves(a.state.params),
                    jax.tree.leaves(b.state.params)):
        assert bool(jnp.all(x == y))               # bitwise, not approx
    assert [r.outer_step for r in recs_a] == [r.outer_step for r in recs_b]


def test_buffered_flush_matches_sequential_with_drops():
    deltas = _delta_list(7)
    for method in ("heloco", "delayed_nesterov", "dcasgd"):
        cfg = OuterOptConfig(method=method, delay_weighting=True,
                             drop_stale_after=1)
        a = Synchronizer(_params(), cfg, n_workers=4, telemetry=True)
        b = Synchronizer(_params(), cfg, n_workers=4, telemetry=True,
                         commit_batch=3)
        recs_a, recs_b = [], []
        for i, d in enumerate(deltas):
            s_i = max(0, i - (i % 3))              # staleness 0..2 -> drops
            recs_a.append(a.on_arrival(d, s_i, i % 4, commit_key=("k", i)))
            out = b.buffer_arrival(d, s_i, i % 4, commit_key=("k", i))
            if out:
                recs_b.extend(out)
        recs_b.extend(b.flush())
        assert a.t == b.t
        for x, y in zip(jax.tree.leaves(a.state.params),
                        jax.tree.leaves(b.state.params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=5e-6, rtol=1e-5,
                                       err_msg=method)
        for x, y in zip(recs_a, recs_b):
            assert (x.outer_step, x.worker_id, x.staleness, x.dropped,
                    x.lang) == (y.outer_step, y.worker_id, y.staleness,
                                y.dropped, y.lang)
            assert x.rho == pytest.approx(y.rho)


def test_idempotent_redelivery_while_buffered():
    cfg = OuterOptConfig(method="heloco")
    s = Synchronizer(_params(), cfg, n_workers=4, commit_batch=8)
    d = _delta_list(1)[0]
    s.buffer_arrival(d, 0, 0, commit_key=("a", 0))
    s.buffer_arrival(d, 0, 0, commit_key=("a", 0))   # dup while pending
    assert s.pending == 1
    assert len(s.flush()) == 1 and s.t == 1
    # dup after commit: ledger short-circuits, nothing re-buffers
    assert s.buffer_arrival(d, 0, 0, commit_key=("a", 0)) is None
    assert s.pending == 0 and s.flush() == [] and s.t == 1


# ---------------------------------------------------------------------------
# Event queue: order, batching, compaction under a storm
# ---------------------------------------------------------------------------

def test_pop_batch_preserves_global_event_order():
    q = EventQueue()
    q.push(1.0, "return", 0, 0)
    q.push(1.0, "return", 1, 0)
    q.push(1.0, "restart", 2, 1)     # same tick, seq-interleaved
    q.push(1.0, "return", 3, 0)
    batch = q.pop_batch(8)           # stops BEFORE the restart
    assert [(w, k) for _, k, w, _ in batch] == [(0, "return"),
                                                (1, "return")]
    assert [k for _, k, _, _ in q.pop_batch(8)] == ["restart"]
    assert [w for _, _, w, _ in q.pop_batch(8)] == [3]


def test_queue_compacts_under_crash_rejoin_storm_n1000():
    """N=1k storm: orphaned in-flight returns must be compacted away
    (never quadratically re-popped) once they outnumber live entries."""
    n = 1000
    q = EventQueue()
    alive_gen = {w: 0 for w in range(n)}
    for w in range(n):
        q.push(1.0 + (w % 5), "return", w, 0)

    def live(kind, wid, gen):
        return kind == "restart" or alive_gen[wid] == gen

    # storm: 900 workers crash; the engine reports each orphaned round
    for w in range(900):
        alive_gen[w] = 1
        q.note_stale()
        q.maybe_compact(live)
    assert q.compactions >= 1        # dead entries never pile up past n/2
    for w in range(900):             # ...and they all rejoin
        q.push(7.0 + (w % 3), "restart", w, 1)
    # drain: at most a bounded remnant of dead returns can reach a pop
    popped_dead = 0
    while len(q):
        for _, kind, wid, gen in q.pop_batch(64):
            if kind == "return" and alive_gen[wid] != gen:
                popped_dead += 1
                q.note_skip()
    assert popped_dead <= 64          # bounded, not O(storm size)
    assert q.stale_skipped == popped_dead


def test_engine_crash_storm_compacts_and_completes():
    """End-to-end: a two-wave crash/rejoin storm over 40 slow workers
    (their orphaned returns pile up BEHIND the fast survivors' events)
    drives the engine's own compaction, and the run still completes its
    outer-step budget on the 8 survivors."""
    from repro.scenarios.spec import FailureSpec, Scenario
    waves = tuple(FailureSpec(time=t, wid=w, restart_delay=0.25)
                  for t in (0.3, 0.7) for w in range(40))
    scn = Scenario(name="_storm", n_workers=48,
                   worker_paces=(2.0,) * 40 + (0.2,) * 8,
                   outer_steps=30, inner_steps=1, batch_size=1, seq_len=16,
                   commit_batch=8, failures=waves)
    eng = scn.build()
    eng.run(eval_fn=None)
    assert eng.server.t == 30
    assert eng._events.compactions >= 1
    assert eng._events.stale_skipped <= 2 * 48    # bounded by membership


# ---------------------------------------------------------------------------
# Worker arena + history ring
# ---------------------------------------------------------------------------

def test_worker_arena_grows_and_recycles_slots():
    arena = WorkerArena(2)
    slots = [arena.alloc(w) for w in range(5)]     # forces growth
    assert len(set(slots)) == 5 and arena.n_alive() == 5
    arena.cols["pace"][slots[3]] = 9.0
    assert arena.min_alive_pace() == 1.0
    arena.release(slots[0])
    assert arena.n_alive() == 4
    s = arena.alloc(17)                            # recycled slot, defaults
    assert arena.cols["wid"][s] == 17
    assert arena.cols["pace"][s] == 1.0 and arena.cols["alive"][s]


def test_history_ring_bounds_memory_but_counts_everything():
    h = History(window=10)
    for i in range(25):
        h.append_arrival({"outer_step": i + 1})
    assert len(h.arrivals) == 10
    assert h.arrivals[0]["outer_step"] == 16       # oldest kept
    assert h.total_arrivals == 25
    assert h.summary()["outer_steps"] == 25
    assert History().window == HISTORY_WINDOW


# ---------------------------------------------------------------------------
# Hogwild ramp-up + committed pace traces
# ---------------------------------------------------------------------------

def test_batch_rampup_token_accounting():
    from repro.scenarios.registry import get_scenario
    scn = get_scenario("hogwild_rampup")
    base = scn.overridden(name="_flat", batch_rampup=None)
    eng_r, eng_b = scn.build(), base.build()
    eng_r.run(eval_every=scn.outer_steps, eval_fn=None)
    eng_b.run(eval_every=scn.outer_steps, eval_fn=None)
    flat = (eng_b.history.total_arrivals * scn.inner_steps
            * scn.batch_size * scn.seq_len)
    assert eng_b.history.tokens == flat
    # the ramp trains strictly more tokens on the same arrival count,
    # bounded by the target batch
    assert eng_r.history.total_arrivals == eng_b.history.total_arrivals
    cap = (eng_r.history.total_arrivals * scn.inner_steps
           * scn.batch_rampup * scn.seq_len)
    assert flat < eng_r.history.tokens <= cap


def test_pace_trace_drives_paces_and_churn():
    from repro.scenarios.registry import get_scenario
    from repro.scenarios.spec import load_pace_trace
    scn = get_scenario("trace_paced")
    tr = load_pace_trace(scn.pace_trace)
    assert scn.paces == tuple(tr["paces"][i % len(tr["paces"])]
                              for i in range(scn.n_workers))
    m = scn.materialize()
    assert any(f.wid == 4 for f in m.failures)     # from the trace file
    acts = {(e.action, e.wid) for e in m.elastic}
    assert ("join", 11) in acts and ("leave", 6) in acts
    assert m.run_cfg.commit_batch == 4
