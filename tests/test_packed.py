"""Packed arrival fast path: layout round-trips, numerical equivalence to
the per-leaf reference (block_correct + outer_update), O(1)-launch
accounting, dropped-arrival fast path, and packed int8 compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import HeLoCoConfig, OuterOptConfig
from repro.core import packing
from repro.core.compression import (
    packed_int8_roundtrip, roundtrip_with_error_feedback,
)
from repro.core.heloco import (
    apply_arrival, apply_arrival_packed, init_outer_state,
    momentum_decay_update,
)
from repro.async_engine.server import Synchronizer
from repro.kernels import ops
from repro.kernels import packed as pk
from repro.kernels.tiling import LANES, ROW_ALIGN, ROWS, padded_rows, row_tile
from repro.obs.spans import SpanTracer, program_build_listener

H = HeLoCoConfig()

# awkward sizes around every padding boundary (satellite: _to_2d property)
AWKWARD_SIZES = [1, 127, 128, 129, LANES * ROWS - 1, LANES * ROWS,
                 LANES * ROWS + 1, LANES * (ROWS + ROW_ALIGN)]


def _tree(key, bf16=False):
    """Multi-leaf transformer-ish pytree incl. a stacked layer axis."""
    ks = jax.random.split(key, 5)
    dt = jnp.bfloat16 if bf16 else jnp.float32
    return {
        "emb": jax.random.normal(ks[0], (40, 30)).astype(dt),
        "layers": {"w": jax.random.normal(ks[1], (3, 4, 5)).astype(dt),
                   "b": jax.random.normal(ks[2], (3, 5)).astype(dt)},
        "norm": jax.random.normal(ks[3], (129,)).astype(dt),
        "head": jax.random.normal(ks[4], (17,)).astype(dt),
    }


STACKED = {"emb": 0, "layers": {"w": 1, "b": 1}, "norm": 0, "head": 0}


def _allclose_tree(a, b, **tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), **tol)


# ---------------------------------------------------------------------------
# _to_2d / tiling (satellite: simplified padding, bounded over-pad)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", AWKWARD_SIZES)
def test_to_2d_roundtrip_and_padding_bound(n):
    x = jnp.arange(1.0, n + 1.0)
    x2d, n_out = ops._to_2d(x)
    assert n_out == n
    r = x2d.shape[0]
    assert x2d.shape[1] == LANES
    assert r % row_tile(r) == 0          # kernel grid always divides
    # over-padding bounded by one sublane tile of rows (old rule hit ~2x)
    assert r * LANES - n < LANES * ROW_ALIGN + LANES
    back = ops._from_2d(x2d, n, x.shape, x.dtype)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    # padding must be zeros (stats kernels rely on it)
    assert not np.any(np.asarray(x2d.reshape(-1)[n:]))


@pytest.mark.parametrize("n", [1, 127, 129, LANES * ROWS - 1,
                               LANES * ROWS + 1])
def test_per_leaf_kernels_at_awkward_sizes(n):
    """The gcd row-tile path must stay exact at non-divisible sizes."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(n))
    u = jax.random.normal(k1, (n,))
    v = jax.random.normal(k2, (n,))
    got = ops.heloco_correct_block(u, v, H, interpret=True)
    from repro.kernels.ref import ref_heloco_correct
    want = ref_heloco_correct(u, v, H)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("n", AWKWARD_SIZES)
def test_packed_layout_roundtrip_awkward(n):
    tree = {"x": jnp.arange(1.0, n + 1.0), "y": jnp.ones((3, 5))}
    layout = packing.build_layout(tree)
    buf = packing.pack(layout, tree)
    assert buf.shape == (layout.n_rows, LANES)
    assert layout.n_rows % row_tile(layout.n_rows) == 0
    back = packing.unpack(layout, buf)
    _allclose_tree(tree, back, rtol=0, atol=0)


def test_packed_layout_stacked_blocks_and_ids():
    layout = packing.build_layout(_tree(jax.random.PRNGKey(0)), STACKED)
    # 1 (emb) + 3 (layers.b) + 3 (layers.w) + 1 (head) + 1 (norm) blocks
    # (pytree flatten order is sorted dict keys)
    assert layout.n_blocks == 9
    rb = layout.row_block
    assert rb.shape == (layout.n_rows,)
    # block ids are sorted and every non-filler block owns >= 1 row
    assert sorted(set(rb.tolist())) == list(range(layout.n_blocks))
    sizes = layout.block_sizes
    assert int(sizes.sum()) == layout.total_elems


def test_pack_unpack_preserves_bf16_leaf_dtypes():
    tree = _tree(jax.random.PRNGKey(1), bf16=True)
    layout = packing.build_layout(tree, STACKED)
    back = packing.unpack(layout, packing.pack(layout, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# Numerical equivalence: packed pipeline vs per-leaf reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["heloco", "mla", "nesterov", "dcasgd"])
def test_packed_arrival_equals_per_leaf(method):
    key = jax.random.PRNGKey(0)
    params = _tree(key)
    delta = _tree(jax.random.PRNGKey(7))
    mom = jax.tree.map(lambda x: -0.3 * x + 0.1, delta)
    state = init_outer_state(params)._replace(momentum=mom)
    layout = packing.build_layout(params, STACKED)
    pbuf = packing.pack(layout, state.params)
    mbuf = packing.pack(layout, state.momentum)

    ref = apply_arrival(state, delta, method=method, outer_lr=0.7, mu=0.9,
                        h=H, rho=0.447, tau=3.0, stacked_axes=STACKED)
    p2, m2 = apply_arrival_packed(pbuf, mbuf, delta, layout, method=method,
                                  outer_lr=0.7, mu=0.9, h=H, rho=0.447,
                                  tau=3.0)
    _allclose_tree(ref.params, packing.unpack(layout, p2),
                   rtol=3e-5, atol=3e-5)
    _allclose_tree(ref.momentum, packing.unpack(layout, m2, jnp.float32),
                   rtol=3e-5, atol=3e-5)


def test_packed_synchronizer_trajectory_matches_per_leaf():
    """Multi-arrival trajectory incl. a dropped stale update."""
    params = _tree(jax.random.PRNGKey(2))
    cfg = OuterOptConfig(method="heloco", drop_stale_after=2)
    svA = Synchronizer(jax.tree.map(jnp.copy, params), cfg, 3,
                       stacked_axes=STACKED, packed=True)
    svB = Synchronizer(jax.tree.map(jnp.copy, params), cfg, 3,
                       stacked_axes=STACKED, packed=False)
    assert svA.packed and not svB.packed
    for i in range(6):
        delta = jax.tree.map(
            lambda x: 0.01 * jax.random.normal(jax.random.PRNGKey(i),
                                               x.shape), params)
        ra = svA.on_arrival(jax.tree.map(jnp.copy, delta),
                            s_i=max(0, svA.t - 3), worker_id=0)
        rb = svB.on_arrival(jax.tree.map(jnp.copy, delta),
                            s_i=max(0, svB.t - 3), worker_id=0)
        assert ra.dropped == rb.dropped
    assert any(r.dropped for r in svA.records)
    assert svA.t == svB.t == 6
    _allclose_tree(svA.state.params, svB.state.params, rtol=3e-5, atol=3e-5)
    _allclose_tree(svA.state.momentum, svB.state.momentum,
                   rtol=3e-5, atol=3e-5)
    # worker_init (packed look-ahead) agrees too
    _allclose_tree(svA.worker_init(), svB.worker_init(),
                   rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("method", ["heloco", "mla", "nesterov", "dcasgd",
                                    "delayed_nesterov"])
def test_momentum_decay_equals_zero_gradient_arrival(method):
    """Dropped-arrival fast path == the method applied to a ZERO
    pseudo-gradient (the pre-fast-path semantics) — including MLA, whose
    momentum extrapolation of a zero delta is a nonzero G."""
    params = _tree(jax.random.PRNGKey(3))
    mom = jax.tree.map(lambda x: 0.1 * x, params)
    state = init_outer_state(params)._replace(momentum=mom)
    zeros = jax.tree.map(jnp.zeros_like, params)
    want = apply_arrival(state, zeros, method=method, outer_lr=0.7, mu=0.9,
                         h=H, rho=0.447, tau=4.0, stacked_axes=STACKED)
    got = momentum_decay_update(state, 0.7, 0.9, method=method, rho=0.447,
                                tau=4.0)
    _allclose_tree(want.params, got.params, rtol=1e-6, atol=1e-6)
    _allclose_tree(want.momentum, got.momentum, rtol=1e-6, atol=1e-6)
    assert int(got.step) == 1


def test_packed_state_checkpoint_roundtrip():
    """state property/setter round-trips bit-exactly (ckpt semantics)."""
    params = _tree(jax.random.PRNGKey(4))
    sv = Synchronizer(params, OuterOptConfig(), 3, stacked_axes=STACKED)
    delta = jax.tree.map(lambda x: 0.01 * x, params)
    sv.on_arrival(delta, s_i=0, worker_id=0)
    snap = sv.state
    sv2 = Synchronizer(params, OuterOptConfig(), 3, stacked_axes=STACKED)
    sv2.state = snap
    assert sv2.t == sv.t == 1
    for a, b in zip(jax.tree.leaves(sv.state.params),
                    jax.tree.leaves(sv2.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_multi_step_grid_matches_single_step():
    """The TPU path walks multi-step grids; interpret mode defaults to one
    step. The explicit rows= override must give identical results, which
    exercises every kernel's index maps."""
    from repro.kernels import heloco_correct as hk
    from repro.kernels import outer_update as ok
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    r = 64
    u = jax.random.normal(ks[0], (r, LANES))
    v = jax.random.normal(ks[1], (r, LANES))
    g = jax.random.normal(ks[2], (r, LANES))
    np.testing.assert_allclose(
        np.asarray(hk.block_stats(u, v, interpret=True).sum(0)),
        np.asarray(hk.block_stats(u, v, interpret=True, rows=8).sum(0)),
        rtol=1e-5, atol=1e-5)
    cu = jnp.asarray(0.7)
    cv = jnp.asarray(-0.2)
    np.testing.assert_allclose(
        np.asarray(hk.correct_apply(u, v, cu, cv, interpret=True)),
        np.asarray(hk.correct_apply(u, v, cu, cv, interpret=True, rows=8)),
        rtol=1e-5, atol=1e-6)
    a1, b1 = ok.outer_update_2d(u, v, g, 0.7, 0.9, 1.0, interpret=True)
    a2, b2 = ok.outer_update_2d(u, v, g, 0.7, 0.9, 1.0, interpret=True,
                                rows=16)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(pk.packed_row_stats(u, v, interpret=True)),
        np.asarray(pk.packed_row_stats(u, v, interpret=True, rows=8)),
        rtol=1e-5, atol=1e-5)
    cur = jnp.ones((r, 1))
    cvr = 0.5 * jnp.ones((r, 1))
    p1, m1 = pk.packed_correct_outer(u, v, g, cur, cvr, 0.7, 0.9, 1.0,
                                     interpret=True)
    p2, m2 = pk.packed_correct_outer(u, v, g, cur, cvr, 0.7, 0.9, 1.0,
                                     interpret=True, rows=16)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# O(1) kernel launches per arrival
# ---------------------------------------------------------------------------

def test_packed_arrival_is_two_launches():
    params = _tree(jax.random.PRNGKey(5))
    delta = _tree(jax.random.PRNGKey(6))
    layout = packing.build_layout(params, STACKED)
    pbuf = packing.pack(layout, params)
    mbuf = packing.zeros(layout)

    n_packed = pk.count_launches(
        lambda: apply_arrival_packed(pbuf, mbuf, delta, layout,
                                     method="heloco", outer_lr=0.7, mu=0.9,
                                     h=H))
    assert n_packed == 2, n_packed   # stats sweep + fused correct+outer

    # per-leaf kernel path: 2 launches per block, independent of d
    state = init_outer_state(params)
    n_leaf = pk.count_launches(
        lambda: apply_arrival(state, delta, method="heloco", outer_lr=0.7,
                              mu=0.9, h=H, stacked_axes=STACKED,
                              use_kernel=True))
    assert n_leaf >= 2 * len(jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Packed int8 compression
# ---------------------------------------------------------------------------

def test_packed_int8_matches_per_leaf_roundtrip():
    params = {"a": jax.random.normal(jax.random.PRNGKey(0), (40, 30)),
              "b": {"c": jax.random.normal(jax.random.PRNGKey(1), (17,))}}
    layout = packing.build_layout(params)
    delta = jax.tree.map(lambda x: 0.5 * x, params)
    dec_p, ef_p, nb_p = roundtrip_with_error_feedback(delta, None, "int8",
                                                      layout=layout)
    dec_l, ef_l, nb_l = roundtrip_with_error_feedback(delta, None, "int8")
    assert nb_p == nb_l              # same wire-byte accounting
    # decoded arrives as an already-packed buffer (no unpack/re-pack on
    # the arrival hot path); pack() must unwrap it for free
    assert isinstance(dec_p, packing.Packed)
    assert packing.pack(layout, dec_p) is dec_p.buf
    _allclose_tree(packing.unpack(layout, dec_p.buf), dec_l,
                   rtol=1e-6, atol=1e-6)
    # error feedback accumulates in the packed buffer and stays unbiased:
    # decoded(delta + ef) + new_ef == delta + ef
    assert ef_p.shape == (layout.n_rows, 128)
    dbuf = packing.pack(layout, delta)
    np.testing.assert_allclose(np.asarray(dec_p.buf + ef_p),
                               np.asarray(dbuf), rtol=1e-6, atol=1e-6)


def test_packed_int8_stacked_scales_per_block():
    """Stacked leaves quantize per LAYER block: a huge layer-0 magnitude
    must not destroy layer-2's resolution (per-leaf scale would)."""
    w = jnp.stack([1000.0 * jnp.ones((4, 5)), jnp.ones((4, 5)),
                   0.001 * jnp.ones((4, 5))])
    tree = {"w": w}
    layout = packing.build_layout(tree, {"w": 1})
    dec_buf, _, _ = roundtrip_with_error_feedback(tree, None, "int8",
                                                  layout=layout)
    dec = packing.unpack(layout, dec_buf.buf)
    # layer 2 survives with its own scale (per-leaf scale 1000/127 would
    # round 0.001 to zero)
    np.testing.assert_allclose(np.asarray(dec["w"][2]), 0.001, rtol=0.01)


def _int8_tree(key, extra=5 * LANES):
    """``_tree`` plus one leaf of ``extra`` elements: with the default the
    layout has 24 rows (three 8-row grid steps) in 10 blocks."""
    return {**_tree(key), "extra": jax.random.normal(
        jax.random.fold_in(key, 7), (extra,))}


_INT8_STACKED = {**STACKED, "extra": 0}


def _eager_int8_roundtrip(layout, delta, ef, rows):
    """The packed int8 round trip as eager ops, one dispatch each."""
    dbuf = packing.pack(layout, delta)
    target = dbuf if ef is None else dbuf + ef
    rowabs = pk.packed_rowabs(target, interpret=True, rows=rows)[:, 0]
    blockabs = jnp.stack([rowabs[s:e].max()
                          for s, e in layout.block_row_ranges])
    scale = jnp.maximum(blockabs, 1e-12) / 127.0
    scale_rows = scale[jnp.asarray(layout.row_block)][:, None]
    q = pk.packed_quant(target, scale_rows, interpret=True, rows=rows)
    decoded = pk.packed_dequant(q, scale_rows, interpret=True, rows=rows)
    return decoded, target - decoded


@pytest.mark.parametrize("rows", [None, 8])
@pytest.mark.parametrize("carried_ef", [False, True])
def test_packed_int8_program_equals_eager_ops(carried_ef, rows):
    """The jitted round trip gives the eager composition's decoded buffer
    and error feedback bit for bit: with and without a carried error,
    stacked-layer blocks, one grid step or three."""
    key = jax.random.PRNGKey(11)
    delta = _int8_tree(key)
    delta["layers"]["w"] = delta["layers"]["w"] * 100.0   # per-block scales
    layout = packing.build_layout(delta, _INT8_STACKED)
    assert layout.n_rows == 24 and layout.n_blocks == 10
    ef = None
    if carried_ef:
        ef = 0.01 * packing.pack(layout, _int8_tree(jax.random.PRNGKey(12)))
    decoded, new_ef, nbytes = packed_int8_roundtrip(
        delta, ef, layout, interpret=True, rows=rows)
    want_decoded, want_ef = _eager_int8_roundtrip(layout, delta, ef, rows)
    np.testing.assert_array_equal(np.asarray(decoded),
                                  np.asarray(want_decoded))
    np.testing.assert_array_equal(np.asarray(new_ef), np.asarray(want_ef))
    assert nbytes == layout.total_elems + 4 * layout.n_blocks


def test_packed_int8_program_builds_once_per_layout():
    """Repeated round trips with one layout lower nothing after the first;
    a second layout lowers its program once."""
    def builds(calls):
        tr = SpanTracer()
        listen = program_build_listener(tr)
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            for delta, ef, layout in calls:
                jax.block_until_ready(packed_int8_roundtrip(delta, ef,
                                                            layout)[:2])
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        return [e for e in tr.to_chrome()["traceEvents"]
                if e.get("name") == "program_build"]

    delta = _int8_tree(jax.random.PRNGKey(0))
    layout = packing.build_layout(delta, _INT8_STACKED)
    _, ef, _ = packed_int8_roundtrip(delta, None, layout)
    builds([(delta, ef, layout)])                       # first: may lower
    again = [(jax.tree.map(lambda x: x * (i + 2.0), delta), ef, layout)
             for i in range(2)]
    assert builds(again) == []
    # a leaf size no other test uses: a layout this process has not seen
    other = _int8_tree(jax.random.PRNGKey(1), extra=3 * LANES + 1)
    other_layout = packing.build_layout(other, _INT8_STACKED)
    new = builds([(other, None, other_layout)])
    assert [e["args"]["fun"] for e in new] == ["jit(_packed_int8_program)"]
