"""Graph container / partition invariants across both edge layouts."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graph import generators as gen
from repro.graph.structs import partition


def _edge_key(g):
    return np.sort(g.src.astype(np.int64) * g.n + g.dst)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.booleans())
def test_symmetrized_idempotent(seed, weighted):
    g = gen.powerlaw(150, avg_deg=5, seed=seed % 97, weighted=weighted)
    s1 = g.symmetrized()
    s2 = s1.symmetrized()
    np.testing.assert_array_equal(_edge_key(s1), _edge_key(s2))
    if weighted:
        o1 = np.argsort(s1.src.astype(np.int64) * g.n + s1.dst)
        o2 = np.argsort(s2.src.astype(np.int64) * g.n + s2.dst)
        np.testing.assert_array_equal(s1.weight[o1], s2.weight[o2])


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_symmetrized_weight_symmetric(seed):
    g = gen.powerlaw(150, avg_deg=5, seed=seed % 89,
                     weighted=True).symmetrized()
    w_of = {}
    for s, d, w in zip(g.src, g.dst, g.weight):
        w_of[(int(s), int(d))] = float(w)
    for (s, d), w in w_of.items():
        assert (d, s) in w_of, "missing reverse edge"
        assert w_of[(d, s)] == w, "asymmetric weight"


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([2, 4, 8]),
       st.sampled_from([None, 6, 16]))
def test_partition_conserves_edges_and_degrees(seed, M, tau):
    g = gen.powerlaw(200, avg_deg=6, seed=seed % 83,
                     weighted=True).symmetrized()
    for layout in ("padded", "csr"):
        pg = partition(g, M, tau=tau, seed=seed % 7, layout=layout)
        # every edge appears exactly once in the full adjacency,
        # and exactly once in the Ch_msg/mirror split
        n_all = int(np.asarray(pg.all_mask).sum())
        n_eg = int(np.asarray(pg.eg_mask).sum())
        n_mir = int(np.asarray(pg.mir_emask).sum())
        assert n_all == g.m, layout
        assert n_eg + n_mir == g.m, layout
        # degrees survive the relabeling
        deg = np.zeros(pg.n_pad, np.int64)
        deg[: g.n] = np.bincount(pg.perm[g.src], minlength=g.n)
        np.testing.assert_array_equal(np.asarray(pg.deg).reshape(-1), deg)
        assert int(np.asarray(pg.vmask).sum()) == g.n


def test_csr_equals_padded_rows_concatenated():
    """Same seed => same sort => csr flat arrays are exactly the padded
    rows with the padding removed (and local ids globalized)."""
    g = gen.powerlaw(250, avg_deg=6, seed=3, weighted=True).symmetrized()
    M = 4
    pp = partition(g, M, tau=8, seed=0, layout="padded")
    pc = partition(g, M, tau=8, seed=0, layout="csr")
    n_loc = pp.n_loc
    for kind in ("eg", "all"):
        mask = np.asarray(getattr(pp, f"{kind}_mask"))
        src_p = np.asarray(getattr(pp, f"{kind}_src"))
        row_w = np.broadcast_to(np.arange(M)[:, None], mask.shape)
        np.testing.assert_array_equal(
            (row_w * n_loc + src_p)[mask],
            np.asarray(getattr(pc, f"{kind}_src")))
        np.testing.assert_array_equal(
            np.asarray(getattr(pp, f"{kind}_dst"))[mask],
            np.asarray(getattr(pc, f"{kind}_dst")))
        np.testing.assert_array_equal(
            np.asarray(getattr(pp, f"{kind}_w"))[mask],
            np.asarray(getattr(pc, f"{kind}_w")))
        off = getattr(pc, f"{kind}_off")
        np.testing.assert_array_equal(np.diff(off), mask.sum(axis=1))
    # mirror edges: local dst on hosting worker w <-> global w*n_loc + dst
    mmask = np.asarray(pp.mir_emask)
    row_w = np.broadcast_to(np.arange(M)[:, None], mmask.shape)
    np.testing.assert_array_equal(np.asarray(pp.mir_esrc)[mmask],
                                  np.asarray(pc.mir_esrc))
    np.testing.assert_array_equal(
        (row_w * n_loc + np.asarray(pp.mir_edst))[mmask],
        np.asarray(pc.mir_edst))
    np.testing.assert_array_equal(np.diff(pc.mir_eoff), mmask.sum(axis=1))
    # per-worker slices really belong to that worker
    for w in range(M):
        sl = slice(int(pc.all_off[w]), int(pc.all_off[w + 1]))
        assert (np.asarray(pc.all_src[sl]) // n_loc == w).all()


EDGE_FIELDS = ("eg_src", "eg_dst", "eg_mask", "eg_w", "all_src", "all_dst",
               "all_mask", "all_w", "mir_esrc", "mir_edst", "mir_emask",
               "mir_ew", "eg_pw", "all_pw", "mir_pw")


@pytest.mark.parametrize("layout,balance", [("padded", "hash"),
                                            ("csr", "hash"),
                                            ("csr", "split"),
                                            ("csr", "fold")])
def test_edge_arrays_stay_on_host(layout, balance):
    """Edge-sized arrays are host NumPy, fresh and after a fold: the
    sharded executor hands each device its slice, so none may sit whole
    on the default device."""
    from repro.graph.structs import EdgeDelta, fold_delta
    g = gen.powerlaw(300, avg_deg=6, seed=5, weighted=True).symmetrized()
    pg = partition(g, 4, tau=8, seed=0, layout=layout,
                   balance="hash" if balance == "fold" else balance)
    if balance == "fold":
        pg = fold_delta(pg, EdgeDelta(add_src=np.array([0, 1]),
                                      add_dst=np.array([2, 3]),
                                      add_w=np.ones(2, np.float32),
                                      rem_src=g.src[:3], rem_dst=g.dst[:3]))
    for name in EDGE_FIELDS:
        v = getattr(pg, name)
        assert v is None or type(v) is np.ndarray, (name, type(v))


def test_partition_rejects_unknown_layout():
    g = gen.chain(16)
    with pytest.raises(ValueError):
        partition(g, 2, layout="coo")


def test_pair_counts_bound_routed_traffic():
    """pair_counts[s, d] is exactly the number of distinct (source worker,
    destination vertex) pairs of the full adjacency — the static cap the
    routed sharded exchange sizes its all_to_all lanes from."""
    g = gen.powerlaw(240, avg_deg=6, seed=4, weighted=True).symmetrized()
    for M in (4, 8):
        pg = partition(g, M, tau=10, seed=1, layout="csr")
        pc = pg.pair_counts
        assert pc.shape == (M, M) and (pc >= 0).all()
        src = np.asarray(pg.all_src)
        dst = np.asarray(pg.all_dst)
        pairs = set(zip((src // pg.n_loc).tolist(), dst.tolist()))
        ref = np.zeros((M, M), np.int64)
        for sw, d in pairs:
            ref[sw, d // pg.n_loc] += 1
        np.testing.assert_array_equal(pc, ref)
        # total distinct pairs can never exceed the edge count
        assert pc.sum() == len(pairs) <= g.m


def test_affinity_groups_recover_planted_host_blocks():
    """Host-topology-aware placement: ``affinity_groups`` must put
    heavy-communicating worker pairs in one host block.  A planted
    two-community affinity matrix (heavy within the communities,
    noise elsewhere) is recovered exactly."""
    from repro.core import cost_model

    rng = np.random.RandomState(0)
    M, H = 8, 2
    groups = [(0, 3, 5, 6), (1, 2, 4, 7)]
    aff = rng.randint(0, 3, (M, M)).astype(np.int64)
    for grp in groups:
        for i in grp:
            for j in grp:
                if i != j:
                    aff[i, j] += 100
    aff = aff + aff.T
    np.fill_diagonal(aff, 0)
    order = cost_model.affinity_groups(aff, H)
    blocks = {frozenset(order[:4].tolist()), frozenset(order[4:].tolist())}
    assert blocks == {frozenset(g) for g in groups}


def test_partition_hosts_is_placement_only_and_never_worse():
    """``partition(hosts=H)`` relabels workers only: the vertex->worker
    *content* is a permutation of the host-oblivious partition (same
    sorted per-worker loads, same edges), and the intra-host share of
    the worker-pair traffic matrix is >= the oblivious contiguous
    grouping's (affinity_groups falls back to identity, so host-aware
    placement can never lose in its own proxy)."""
    from repro.core import cost_model

    g = gen.powerlaw(300, avg_deg=6, seed=3, weighted=True).symmetrized()
    M, H = 8, 2
    T = M // H

    def intra(pc):
        aff = cost_model.worker_affinity(pc)
        return sum(aff[h * T:(h + 1) * T, h * T:(h + 1) * T].sum()
                   for h in range(H))

    for balance in ("hash", "edges"):
        base = partition(g, M, tau=10, seed=1, layout="csr",
                         balance=balance)
        host = partition(g, M, tau=10, seed=1, layout="csr",
                         balance=balance, hosts=H)
        assert base.hosts is None and host.hosts == H
        # placement only: same multiset of per-worker edge loads, every
        # edge conserved
        assert sorted(base.edge_load().tolist()) == \
            sorted(host.edge_load().tolist())
        assert np.asarray(host.all_src).shape == \
            np.asarray(base.all_src).shape
        assert len(set(host.perm.tolist())) == g.n
        assert 0 <= host.perm.min() and host.perm.max() < M * host.n_loc
        # host-aware grouping never scores below the oblivious order
        assert intra(host.pair_counts) >= intra(base.pair_counts)

    with pytest.raises(ValueError):
        partition(g, M, hosts=3)
