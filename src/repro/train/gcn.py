"""2-layer GCN trained end-to-end on the sharded graph executor.

Every neighbourhood aggregation is a gSpMM channel join
(:mod:`repro.core.gspmm`): the (lanes, F) feature blocks ride the same
Ch_msg sender-side combining + Ch_mir mirror fan-out the analytics
algorithms use, so the paper's message-reduction machinery is the GNN's
message-passing layer.  Forward, per layer::

    H' = act( u_mul_e_sum(A_hat, H) @ W + b )

with ``A_hat`` the symmetrically normalized adjacency
(:func:`normalize_adjacency` — D^-1/2 A D^-1/2, symmetric, so the
custom-VJP self-adjoint backward join applies).

Differentiation inside ``shard_map`` follows the executor's gradient
contract (verified by tests/test_gspmm.py):

* the loss each device differentiates is its LOCAL masked sum — never a
  ``psum``.  Differentiating through ``psum`` under ``check_vma=False``
  multiplies cotangents by the device count; and no psum is needed,
  because the join's backward pass is itself a collective that routes
  every device's cotangent contributions to the owning rows.
* the sharded embedding grad is therefore already complete per device;
* replicated dense-parameter grads (W, b) cover only the device's rows
  and are ``psum``-reduced AFTER ``jax.grad``;
* global-norm clipping needs the cross-device norm: the sharded leaf's
  squared norm is psum'd, replicated leaves' are not.

The step is built ONCE via :func:`repro.core.exec.build_apply`
(``out_rule="auto"`` + an explicit ``is_sharded`` predicate, since a
replicated weight matrix's leading dim may coincide with ``M``) and the
epoch loop re-invokes the jitted function.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api import EngineConfig, RunResult
from repro.core import gspmm
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state

_REPLICATED = ("W1", "b1", "W2", "b2")


def normalize_adjacency(g):
    """Symmetric GCN normalization on a symmetrized Graph:
    w'(u,v) = w(u,v) / sqrt(d(u) d(v)) with unweighted degrees — still
    symmetric, so the segment-sum joins stay self-adjoint."""
    import numpy as np
    from repro.graph.structs import Graph
    deg = np.maximum(g.out_degrees(), 1).astype(np.float64)
    w = g.weight if g.weight is not None else np.ones(g.m, np.float32)
    wn = (w / np.sqrt(deg[g.src] * deg[g.dst])).astype(np.float32)
    return Graph(g.n, g.src, g.dst, wn)


def gcn_labels(pg, n_classes: int, seed: int = 0):
    """Synthetic per-vertex class labels, a function of the ORIGINAL
    vertex id (partition-independent).  Returns ``(labels, mask)`` shaped
    ``(M, n_loc)``; padding slots carry label 0 with mask False."""
    import numpy as np
    rng = np.random.RandomState(seed + 7)
    lab = rng.randint(0, n_classes, size=pg.n).astype(np.int32)
    full = np.zeros(pg.n_pad, np.int32)
    full[np.asarray(pg.perm)] = lab
    labels = jnp.asarray(full).reshape(pg.M, pg.n_loc)
    mask = jnp.asarray(pg.vmask).reshape(pg.M, pg.n_loc)
    return labels, mask


def init_gcn_params(pg, feat_dim: int, hidden: int, n_classes: int,
                    seed: int = 0):
    """{emb (M, n_loc, F) sharded; W1 (F, H), b1, W2 (H, C), b2
    replicated} — Glorot-ish scaling."""
    import numpy as np
    from repro.models.embedding import node_embedding_init
    rng = np.random.RandomState(seed)
    s1 = (2.0 / (feat_dim + hidden)) ** 0.5
    s2 = (2.0 / (hidden + n_classes)) ** 0.5
    return {
        "emb": node_embedding_init(pg, feat_dim, seed=seed),
        "W1": jnp.asarray(rng.randn(feat_dim, hidden).astype(np.float32)
                          * s1),
        "b1": jnp.zeros((hidden,), jnp.float32),
        "W2": jnp.asarray(rng.randn(hidden, n_classes).astype(np.float32)
                          * s2),
        "b2": jnp.zeros((n_classes,), jnp.float32),
    }


def gcn_forward(gctx, params, backend: str = "dense",
                use_mirroring: bool = True):
    """Two joins, two dense layers.  ``gctx`` is the PartitionedGraph or
    the device-local ShardedGraph inside a ``shard_map`` body."""
    fj = gspmm.gspmm_join(gctx, "u_mul_e_sum", backend=backend,
                          use_mirroring=use_mirroring)
    h = fj(params["emb"])
    h = jax.nn.relu(h @ params["W1"] + params["b1"])
    h = fj(h)
    return h @ params["W2"] + params["b2"]


def _xent_sum(logits, labels, mask):
    """Masked softmax cross-entropy, SUM over rows (local loss — the
    mean is taken after the psum of counts)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    oh = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    picked = jnp.sum(logits * oh, axis=-1)
    nll = (lse - picked) * mask.astype(logits.dtype)
    return jnp.sum(nll)


def make_gcn_step(cfg: OptConfig, backend: str = "dense",
                  use_mirroring: bool = True):
    """``mk(gctx) -> step(params, opt, labels, mask) ->
    ((new_params, new_opt), metrics)`` — the ``build_apply`` contract."""
    # clipping is applied here with the true cross-device norm; disarm
    # adamw_update's internal (device-local) re-clip
    inner_cfg = dataclasses.replace(cfg, clip_norm=1e30)

    def mk(gctx):
        axis = getattr(gctx, "axis", None)

        def psum_(x):
            return jax.lax.psum(x, axis) if axis is not None else x

        def step(params, opt, labels, mask):
            def loss_fn(p):
                logits = gcn_forward(gctx, p, backend=backend,
                                     use_mirroring=use_mirroring)
                return _xent_sum(logits, labels, mask)

            lsum, grads = jax.value_and_grad(loss_fn)(params)
            count = psum_(jnp.sum(mask.astype(jnp.float32)))
            loss = psum_(lsum) / count
            # emb grad is complete per device (collective backward join);
            # dense-param grads only saw this device's rows
            grads = {k: (v if k == "emb" else psum_(v))
                     for k, v in grads.items()}
            grads = jax.tree.map(lambda g_: g_ / count, grads)
            # cross-device global norm: psum the sharded leaf's sumsq only
            sumsq = {k: jnp.sum(jnp.square(v)) for k, v in grads.items()}
            gn2 = psum_(sumsq["emb"]) + sum(sumsq[k] for k in _REPLICATED)
            gnorm = jnp.sqrt(gn2)
            scale = jnp.minimum(1.0, cfg.clip_norm
                                / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree.map(lambda g_: g_ * scale, grads)
            new_params, new_opt, m = adamw_update(params, grads, opt,
                                                  inner_cfg)
            return ((new_params, new_opt),
                    {"loss": loss, "grad_norm": gnorm, "lr": m["lr"]})

        return step

    return mk


def run(pg, config: EngineConfig | None = None, *, feat_dim: int = 32,
        hidden: int = 64, n_classes: int = 8, epochs: int = 10,
        lr: float = 1e-2, seed: int = 0,
        params: Optional[dict] = None) -> RunResult:
    """GCN training under an EngineConfig: ``state`` is the trained
    params dict, ``history`` the loss trajectory, ``n_supersteps`` the
    epoch count.  ``devices=None`` in the config maps to the D=1 mesh
    (training always runs through the sharded executor)."""
    cfg = config or EngineConfig()
    params, losses = train_gcn(
        pg, feat_dim=feat_dim, hidden=hidden, n_classes=n_classes,
        epochs=epochs, lr=lr, seed=seed, backend=cfg.backend,
        devices=cfg.devices if cfg.devices is not None else 1,
        use_mirroring=cfg.use_mirroring, pipeline=cfg.pipeline,
        params=params)
    return RunResult(state=params, stats={}, n_supersteps=epochs,
                     history=losses)


def train_gcn(pg, feat_dim: int = 32, hidden: int = 64,
              n_classes: int = 8, epochs: int = 10, lr: float = 1e-2,
              seed: int = 0, backend: str = "dense", devices=1,
              use_mirroring: bool = True, pipeline: bool = False,
              params: Optional[dict] = None) -> Tuple[dict, list]:
    """Full training run: builds the sharded step once, iterates
    ``epochs`` full-graph AdamW steps, returns ``(params, loss_history)``.
    ``pg`` must be partitioned from a :func:`normalize_adjacency`'d (or
    at least symmetrized) graph."""
    from repro.core import exec as exec_mod

    if params is None:
        params = init_gcn_params(pg, feat_dim, hidden, n_classes, seed)
    opt = init_opt_state(params)
    labels, mask = gcn_labels(pg, n_classes, seed)
    cfg = OptConfig(lr=lr, weight_decay=0.0, clip_norm=1.0,
                    warmup_steps=0, total_steps=max(epochs, 1),
                    min_lr_frac=1.0)
    kinds = (exec_mod.broadcast_plan_kinds(backend, use_mirroring)
             if backend == "pallas" else ())

    def sharded_leaf(x):
        return (getattr(x, "ndim", 0) >= 2
                and x.shape[:2] == (pg.M, pg.n_loc))

    fn, arrays = exec_mod.build_apply(
        pg, make_gcn_step(cfg, backend, use_mirroring),
        (params, opt, labels, mask), devices=devices, plan_kinds=kinds,
        pipeline=pipeline, out_rule="auto", is_sharded=sharded_leaf)

    losses = []
    for _ in range(epochs):
        (params, opt), metrics = fn(arrays, (params, opt, labels, mask))
        losses.append(float(metrics["loss"]))
    return params, losses
