"""Connected-components reference: a NumPy union-find.  Each vertex's
answer is the smallest original id in its component.  Imports nothing of
the program."""
from __future__ import annotations

import numpy as np


def reference(n, src, dst, params):
    """(n,) min original id of each vertex's component: hook the larger
    root under the smaller, compress every path each round (parents only
    decrease, so no cycles form)."""
    parent = np.arange(n, dtype=np.int64)
    s, d = src[src < dst], dst[src < dst]
    while True:
        ps, pd = parent[s], parent[d]
        live = ps != pd
        if not live.any():
            return parent
        s, d, ps, pd = s[live], d[live], ps[live], pd[live]
        np.minimum.at(parent, np.maximum(ps, pd), np.minimum(ps, pd))
        while True:
            up = parent[parent]
            if np.array_equal(up, parent):
                break
            parent = up


def canonical(labels):
    """Any labelling of a partition of the vertices -> the smallest
    vertex id of each vertex's group."""
    labels = np.asarray(labels)
    _, inv = np.unique(labels, return_inverse=True)
    rep = np.full(inv.max() + 1, len(labels), np.int64)
    np.minimum.at(rep, inv, np.arange(len(labels)))
    return rep[inv]


def compare(got, ref):
    """Numbers compared: vertices whose component differs from the
    reference's."""
    return {"sv_wrong_vertices": int(np.count_nonzero(canonical(got) != ref))}


def from_program(state, perm):
    """The program's (M, n_loc) labels in original vertex order."""
    return np.asarray(state).reshape(-1)[perm]
