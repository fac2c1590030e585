"""Integration tests for the asynchronous runtime: scheduling semantics,
DyLU, sync mode, fault injection + recovery, elastic membership,
checkpoint/restore, compression accounting."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import InnerOptConfig, OuterOptConfig, RunConfig
from repro.async_engine.simulator import (
    AsyncSimulator, ElasticEvent, FailureEvent, make_eval_fn,
)


def tiny_run(method="heloco", **kw):
    cfg = reduced(get_config("tinygpt-15m"))
    defaults = dict(
        model=cfg, n_workers=3, inner_steps=3, outer_steps=9,
        batch_size=2, seq_len=16,
        worker_paces=(1.0, 2.0, 6.0), non_iid=True,
        inner=InnerOptConfig(lr=3e-3, warmup_steps=2, total_steps=100),
        outer=OuterOptConfig(method=method))
    defaults.update(kw)
    return RunConfig(**defaults)


def test_async_staleness_asymmetry():
    """Fast workers must contribute more arrivals with lower staleness."""
    sim = AsyncSimulator(tiny_run(outer_steps=15))
    hist = sim.run()
    per_worker = {}
    for a in hist.arrivals:
        per_worker.setdefault(a["worker_id"], []).append(a["staleness"])
    counts = {w: len(v) for w, v in per_worker.items()}
    assert counts[0] > counts[2], counts          # fast contributes more
    assert np.mean(per_worker[2]) > np.mean(per_worker[0])  # slow is staler


def test_dylu_equalizes_contributions():
    sim = AsyncSimulator(tiny_run(outer_steps=18, inner_steps=6, dylu=True))
    hist = sim.run()
    counts = {}
    for a in hist.arrivals:
        counts[a["worker_id"]] = counts.get(a["worker_id"], 0) + 1
    vals = list(counts.values())
    assert max(vals) - min(vals) <= 2, counts     # near-equal participation


def test_sync_mode_barrier_time():
    rc = tiny_run(method="sync_nesterov", outer_steps=4)
    sim = AsyncSimulator(rc)
    hist = sim.run()
    # each round's wall time = slowest worker = 3 steps * 6 s
    assert hist.final_time == pytest.approx(4 * 3 * 6.0)
    assert all(a["staleness"] == 0 for a in hist.arrivals)


def test_failure_recovery_continues_training():
    rc = tiny_run(outer_steps=12)
    failures = [FailureEvent(time=5.0, wid=0, restart_delay=10.0)]
    sim = AsyncSimulator(rc, failures=failures)
    hist = sim.run(eval_every=12, eval_fn=make_eval_fn(sim, batch=2, seq=16))
    assert len(hist.arrivals) == 12
    # worker 0 eventually contributes again after restart
    post = [a for a in hist.arrivals if a["worker_id"] == 0
            and a["sim_time"] > 15.0]
    assert post, "restarted worker never contributed"
    assert np.isfinite(hist.evals[-1]["mean"])


def test_elastic_join_and_leave():
    rc = tiny_run(outer_steps=12)
    elastic = [ElasticEvent(time=4.0, action="join", wid=7, pace=1.0, lang=1),
               ElasticEvent(time=20.0, action="leave", wid=2)]
    sim = AsyncSimulator(rc, elastic=elastic)
    hist = sim.run()
    wids = {a["worker_id"] for a in hist.arrivals}
    assert 7 in wids                              # joined worker contributes
    late = [a for a in hist.arrivals if a["sim_time"] > 21.0]
    assert all(a["worker_id"] != 2 for a in late)  # departed worker silent


def test_checkpoint_restore_bitexact(tmp_path):
    rc = tiny_run(outer_steps=6)
    sim = AsyncSimulator(rc)
    sim.run(ckpt_every=3, ckpt_dir=str(tmp_path))
    path = os.path.join(str(tmp_path), "step_6.npz")
    assert os.path.exists(path)

    sim2 = AsyncSimulator(rc)                     # fresh process semantics
    sim2.restore(path)
    a = jax.tree.leaves(sim.server.state.params)
    b = jax.tree.leaves(sim2.server.state.params)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert sim2.server.t == 6
    # training continues after restore
    sim2.cfg = rc.__class__(**{**rc.__dict__, "outer_steps": 9})
    hist = sim2.run()
    assert sim2.server.t == 9


def test_checkpoint_detects_corruption(tmp_path):
    rc = tiny_run(outer_steps=3)
    sim = AsyncSimulator(rc)
    sim.run()
    path = sim.checkpoint(str(tmp_path))
    with open(path, "r+b") as f:
        f.seek(200)
        f.write(b"\xde\xad\xbe\xef")
    sim2 = AsyncSimulator(rc)
    with pytest.raises(Exception):
        sim2.restore(path)


@pytest.mark.parametrize("kind,max_ratio", [("int8", 0.30), ("topk", 0.35)])
def test_compression_reduces_bytes(kind, max_ratio):
    base = AsyncSimulator(tiny_run(outer_steps=6))
    base_hist = base.run()
    comp = AsyncSimulator(tiny_run(
        outer_steps=6,
        outer=OuterOptConfig(method="heloco", compression=kind,
                             topk_ratio=0.1)))
    comp_hist = comp.run()
    assert comp_hist.comm_bytes < base_hist.comm_bytes * max_ratio
    # still trains
    assert np.isfinite(float(jax.tree.leaves(comp.server.state.params)[0].sum()))


def test_fp32_exchange_keeps_no_error_feedback():
    """Under ``compression: "none"`` error feedback leaves every worker
    without a buffer, and the run equals, bit for bit, the same run with
    error feedback off: its arrival records (with the update statistics)
    and its final outer params."""
    from repro.async_engine.engine import make_engine
    from repro.telemetry import TelemetryRecorder
    runs = []
    for ef in (True, False):
        eng = make_engine(tiny_run(outer_steps=6, outer=OuterOptConfig(
            method="heloco", error_feedback=ef)),
            telemetry=TelemetryRecorder())
        runs.append((eng, eng.run()))
    (on, hist_on), (off, hist_off) = runs
    assert all(w.ef is None for w in on.workers.values())
    assert hist_on.arrivals == hist_off.arrivals
    assert all(r["delta_norm"] is not None for r in hist_on.arrivals)
    for a, b in zip(jax.tree.leaves(on.server.state.params),
                    jax.tree.leaves(off.server.state.params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_drop_stale_after():
    rc = tiny_run(outer_steps=12,
                  outer=OuterOptConfig(method="heloco", drop_stale_after=1),
                  worker_paces=(1.0, 12.0, 12.0))
    sim = AsyncSimulator(rc)
    hist = sim.run()
    dropped = [a for a in hist.arrivals if a["dropped"]]
    assert dropped, "no stale update was dropped"
    assert all(a["staleness"] > 1 for a in dropped)


def test_flexible_assignment_balances_langs():
    rc = tiny_run(outer_steps=12, shard_assignment="flexible",
                  worker_paces=(1.0, 1.0, 8.0))
    sim = AsyncSimulator(rc)
    sim.run()
    toks = sim.lang_tokens[sim.lang_tokens > 0]
    assert toks.max() <= toks.min() * 4  # far tighter than fixed w/ 8x pace gap


def test_socket_transport_refuses_an_accelerator_backend(monkeypatch):
    """A chip belongs to one process: worker processes on an accelerator
    host would each reach for the chip the parent holds."""
    from repro.async_engine import proc
    monkeypatch.setattr(proc.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="transport='inproc'"):
        proc.WorkerProcessPool(tiny_run(n_workers=2))


_PINNED_SCRIPT = r"""
import jax
from repro.async_engine.engine import make_engine
from repro.scenarios.spec import Scenario

scn = Scenario(name="pinned", engine="wallclock", n_workers=4,
               worker_paces=(1.0, 2.0, 3.0, 4.0), inner_steps=1,
               outer_steps=8, batch_size=2, seq_len=16)
m = scn.materialize()
devs = jax.devices()
for pin in (True, False):
    eng = make_engine(m.run_cfg, m.engine, pin_devices=pin, **m.engine_kw)
    made, committed = {}, set()
    execute, commit = eng._execute, eng.server._step_update

    def record(task, execute=execute, made=made):
        res = execute(task)
        made.setdefault(task.wid, set()).update(
            jax.tree.leaves(res.delta)[0].devices())
        return res

    def spy(delta, rho, tau, commit=commit, committed=committed):
        committed.update(jax.tree.leaves(delta)[0].devices())
        return commit(delta, rho, tau)

    eng._execute, eng.server._step_update = record, spy
    hist = eng.run()
    assert sorted(a["worker_id"] for a in hist.arrivals) == \
        [0, 0, 0, 0, 1, 1, 2, 3], hist.arrivals
    want = ({w: {devs[w]} for w in range(4)} if pin
            else {w: {devs[0]} for w in range(4)})
    assert made == want, (pin, made)
    assert committed == {devs[0]}, (pin, committed)
print("PINNED_OK")
"""


def test_pinned_workers_run_on_their_devices_and_commit_on_the_server():
    """One worker per device: each round runs on its worker's device and
    its delta is committed on the server's (4 host devices in a child
    process, so this process keeps its one-device view)."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", _PINNED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PINNED_OK" in out.stdout, out.stderr[-3000:]
