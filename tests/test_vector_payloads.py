"""Vector (lanes, F) message payloads: kernel/plan/channel parity with
the scalar path and with per-feature references.

The refactor's contract is structural: a scalar input evaluates the exact
original expressions, so F=1 must be BITWISE identical to the scalar
path, and an F-block result must equal F independent scalar runs (modulo
nothing — the combine order per feature is unchanged)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import channels
from repro.core import plan as planlib
from repro.graph import generators as gen
from repro.graph.structs import partition
from repro.kernels.segment_combine.kernel import sentinels
from repro.kernels.segment_combine.ops import pack_edges, pack_values
from repro.kernels.segment_combine.ref import segment_combine_blocks_ref
from repro.kernels.segment_combine.kernel import segment_combine_blocks


def _pg(layout="csr", n=180, M=8, tau=8):
    g = gen.powerlaw(n, avg_deg=5, seed=1, weighted=True).symmetrized()
    return partition(g, M, tau=tau, seed=0, layout=layout)


# ---------------------------------------------------------------------------
# kernel: (n_blocks, eb, F) combine vs ref and vs per-feature scalar
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("F", [1, 8, 32, 130])
def test_vector_blocks_vs_ref(op, F):
    # F=130 exceeds one 128-lane feature tile -> exercises the tile loop
    rng = np.random.RandomState(0)
    nb, eb, n_blocks = 128, 256, 3
    idx = rng.randint(-1, nb, (n_blocks, eb)).astype(np.int32)
    vals = rng.randn(n_blocks, eb, F).astype(np.float32)
    out = segment_combine_blocks(jnp.asarray(vals), jnp.asarray(idx), op, nb)
    ref = segment_combine_blocks_ref(jnp.asarray(vals), jnp.asarray(idx),
                                     op, nb)
    assert out.shape == (n_blocks, nb, F)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_vector_blocks_match_per_feature_scalar(op):
    rng = np.random.RandomState(1)
    nb, eb, n_blocks, F = 64, 128, 2, 5
    idx = rng.randint(-1, nb, (n_blocks, eb)).astype(np.int32)
    vals = rng.randn(n_blocks, eb, F).astype(np.float32)
    out = np.asarray(segment_combine_blocks(jnp.asarray(vals),
                                            jnp.asarray(idx), op, nb))
    for f in range(F):
        col = np.asarray(segment_combine_blocks(
            jnp.asarray(vals[:, :, f]), jnp.asarray(idx), op, nb))
        if op == "sum":  # float adds may reassociate across tile shapes
            np.testing.assert_allclose(out[:, :, f], col, rtol=1e-6)
        else:
            np.testing.assert_array_equal(out[:, :, f], col)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_f1_bitwise_identical_to_scalar(op):
    rng = np.random.RandomState(2)
    nb, eb, n_blocks = 128, 256, 2
    idx = rng.randint(-1, nb, (n_blocks, eb)).astype(np.int32)
    vals = rng.randn(n_blocks, eb).astype(np.float32)
    scalar = np.asarray(segment_combine_blocks(jnp.asarray(vals),
                                               jnp.asarray(idx), op, nb))
    vec = np.asarray(segment_combine_blocks(jnp.asarray(vals[..., None]),
                                            jnp.asarray(idx), op, nb))
    np.testing.assert_array_equal(scalar, vec[:, :, 0])


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_int_vector_blocks_exact(op):
    rng = np.random.RandomState(3)
    nb, eb, n_blocks, F = 64, 128, 2, 3
    idx = rng.randint(-1, nb, (n_blocks, eb)).astype(np.int32)
    vals = rng.randint(-1000, 1000, (n_blocks, eb, F)).astype(np.int32)
    out = segment_combine_blocks(jnp.asarray(vals), jnp.asarray(idx), op, nb)
    ref = segment_combine_blocks_ref(jnp.asarray(vals), jnp.asarray(idx),
                                     op, nb)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# half precision: sentinel fallback + signed zeros / infinities
# ---------------------------------------------------------------------------

def test_sentinels_fit_in_dtype():
    """float16's finfo.max (65504) is far below the float32 sentinel
    (3e38): the kernel must fall back to the dtype's own bounds or the
    min/max identity becomes inf and the no-contribution remap breaks."""
    for dt in (jnp.float16, jnp.bfloat16, jnp.float32):
        neg, pos = sentinels(dt)
        assert np.isfinite(np.asarray(jnp.asarray(pos, dt), np.float64))
        assert np.isfinite(np.asarray(jnp.asarray(neg, dt), np.float64))
    assert sentinels(jnp.float16) == (-65504.0, 65504.0)


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_half_precision_zeros_and_inf(dtype, op):
    """Regression: combining +-0.0 (all ops) and +-inf (min/max) in half
    precision.  The pallas kernel must agree with the jnp scatter
    reference (inf saturates to the dtype sentinel under min/max by
    design — the same clamp the reference's identity init applies; the
    sum path is a one-hot contraction in BOTH implementations, where a
    0*inf product is NaN, so infs stay out of the sum leg)."""
    rng = np.random.RandomState(4)
    nb, eb, n_blocks, F = 64, 128, 2, 4
    idx = rng.randint(-1, nb, (n_blocks, eb)).astype(np.int32)
    vals = rng.randn(n_blocks, eb, F).astype(np.float32)
    # sprinkle the awkward values everywhere
    if op == "sum":
        # saturation extremes are out too: the reference's stepwise half
        # rounding diverges from the kernel's fp32 accumulation there
        special = np.array([0.0, -0.0, 1.5, -1.5], np.float32)
    else:
        special = np.array([0.0, -0.0, np.inf, -np.inf], np.float32)
    pick = rng.randint(0, 4, vals.shape)
    use = rng.rand(*vals.shape) < 0.3
    vals = np.where(use, special[pick], vals)
    v = jnp.asarray(vals, dtype)
    out = segment_combine_blocks(v, jnp.asarray(idx), op, nb)
    ref = segment_combine_blocks_ref(v, jnp.asarray(idx), op, nb)
    assert out.dtype == dtype
    o32 = np.asarray(out, np.float32)
    r32 = np.asarray(ref, np.float32)
    if op == "sum":
        # half sums accumulate in fp32 inside the kernel; the reference
        # accumulates in the half dtype — allow half-precision slack
        np.testing.assert_allclose(o32, r32, rtol=2e-2, atol=2e-2)
        assert np.isfinite(o32).all()
    else:
        np.testing.assert_array_equal(o32, r32)


@pytest.mark.parametrize("mode", ["ref", "pallas"])
@pytest.mark.parametrize("op", ["min", "max"])
def test_half_precision_identity_remap(op, mode):
    """The plan-layer sentinel remap in f16: rows with NO contributing
    edge must come back as the CHANNEL identity (+-inf), not the kernel's
    finite f16 sentinel (+-65504) — the regression the sentinel fallback
    fixes: with the canonical 3e38 thresholds (inf in f16) the remap
    comparison could never fire."""
    rng = np.random.RandomState(5)
    N, E = 200, 600
    nb = 64
    dst = rng.randint(0, N // 2, E)  # upper half: no contributions
    vals = (rng.randn(E).astype(np.float16)).astype(np.float16)
    order, idxl = pack_edges(dst, N, nb=nb, eb_align=128)
    pv = pack_values(vals, order, idxl, op)
    old = planlib.kernel_mode()
    planlib.set_kernel_mode(mode)
    try:
        blocks = planlib._combine_rows(jnp.asarray(pv), jnp.asarray(idxl),
                                       op, nb)
    finally:
        planlib.set_kernel_mode(old)
    out = np.asarray(blocks).reshape(-1)[:N]
    ident = np.asarray(planlib.identity_of(op, jnp.float16), np.float16)
    assert np.isinf(ident)
    assert (out[N // 2:] == ident).all()
    red = np.minimum if op == "min" else np.maximum
    ref = np.full(N, ident, np.float16)
    red.at(ref, dst, vals)
    np.testing.assert_array_equal(out[: N // 2], ref[: N // 2])


# ---------------------------------------------------------------------------
# plan + channels: vector payloads vs per-feature scalar runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["padded", "csr"])
@pytest.mark.parametrize("backend", ["dense", "pallas"])
@pytest.mark.parametrize("op", ["sum", "min"])
def test_broadcast_vector_matches_per_feature(layout, backend, op):
    F = 3
    pg = _pg(layout)
    rng = np.random.RandomState(6)
    vals = rng.randn(pg.M, pg.n_loc, F).astype(np.float32)
    act = rng.rand(pg.M, pg.n_loc) > 0.3
    out, stats = channels.broadcast(pg, jnp.asarray(vals), jnp.asarray(act),
                                    op, relay="mul_w", backend=backend)
    assert out.shape == (pg.M, pg.n_loc, F)
    for f in range(F):
        ref, rs = channels.broadcast(pg, jnp.asarray(vals[:, :, f]),
                                     jnp.asarray(act), op, relay="mul_w",
                                     backend=backend)
        np.testing.assert_array_equal(np.asarray(out[:, :, f]),
                                      np.asarray(ref))
        # activity (and thus message accounting) is per LANE, not per
        # feature: the vector join sends one (F,) block per active lane
        for k in ("msgs_total", "msgs_combined", "msgs_mirror"):
            if k in rs:
                np.testing.assert_array_equal(np.asarray(stats[k]),
                                              np.asarray(rs[k]))


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_broadcast_f1_bitwise_identical(backend):
    pg = _pg("csr")
    rng = np.random.RandomState(7)
    vals = rng.randn(pg.M, pg.n_loc).astype(np.float32)
    act = rng.rand(pg.M, pg.n_loc) > 0.3
    s_out, _ = channels.broadcast(pg, jnp.asarray(vals), jnp.asarray(act),
                                  "min", backend=backend)
    v_out, _ = channels.broadcast(pg, jnp.asarray(vals)[..., None],
                                  jnp.asarray(act), "min", backend=backend)
    np.testing.assert_array_equal(np.asarray(s_out),
                                  np.asarray(v_out)[:, :, 0])


def test_gather_vector_matches_per_feature():
    pg = _pg("csr")
    rng = np.random.RandomState(8)
    F, R = 4, 11
    vals = rng.randn(pg.M, pg.n_loc, F).astype(np.float32)
    targets = rng.randint(0, pg.n_pad, (pg.M, R)).astype(np.int32)
    tmask = rng.rand(pg.M, R) > 0.25
    out, _ = channels.gather(pg, jnp.asarray(vals), jnp.asarray(targets),
                             jnp.asarray(tmask))
    assert out.shape == (pg.M, R, F)
    for f in range(F):
        ref, _ = channels.gather(pg, jnp.asarray(vals[:, :, f]),
                                 jnp.asarray(targets), jnp.asarray(tmask))
        np.testing.assert_array_equal(np.asarray(out[:, :, f]),
                                      np.asarray(ref))


def test_node_embedding_fetch_vector_rows():
    from repro.models.embedding import (node_embedding_fetch,
                                        node_embedding_init)
    pg = _pg("csr")
    F, R = 6, 9
    tab = node_embedding_init(pg, F, seed=3)
    assert tab.shape == (pg.M, pg.n_loc, F)
    # padding slots are zero rows
    flat = np.asarray(tab).reshape(pg.n_pad, F)
    valid = np.zeros(pg.n_pad, bool)
    valid[np.asarray(pg.perm)] = True
    assert (flat[~valid] == 0).all()
    rng = np.random.RandomState(9)
    ids = rng.randint(0, pg.n_pad, (pg.M, R)).astype(np.int32)
    mask = rng.rand(pg.M, R) > 0.2
    got, _ = node_embedding_fetch(pg, tab, jnp.asarray(ids),
                                  jnp.asarray(mask))
    ref = flat[ids] * mask[:, :, None]
    np.testing.assert_array_equal(np.asarray(got), ref.astype(np.float32))
