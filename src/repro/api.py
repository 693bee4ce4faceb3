"""The one front door: ``Engine`` + ``EngineConfig`` + ``RunResult``.

Every execution knob the engine understands — backend, edge layout,
balance mode, device mesh, pipelining, mirroring — lives in ONE frozen
``EngineConfig`` instead of being re-plumbed as seven keyword arguments
through every ``algorithms/*.py`` signature, every driver, and every
benchmark.  Algorithms expose a canonical

    run(pg, config, **algo_params) -> RunResult

and the legacy positional-tuple entry points (``hashmin(pg, ...)`` ->
``(labels, stats, n)`` etc.) survive for one PR as thin deprecated
wrappers around it.

    from repro.api import Engine, EngineConfig

    eng = Engine(EngineConfig(backend="pallas", layout="csr", devices=8))
    res = eng.run("pagerank", g, M=64, n_iters=30)
    res.state, res.stats, res.n_supersteps, res.history

``Engine.run`` accepts a host ``Graph`` (partitioned on the fly with the
config's layout/balance; pass ``M``/``tau``/``seed``) or an existing
``PartitionedGraph``.  ``graph_run``, ``shard_check``, ``train/gcn`` and
the resident graph service (``core/service.py``) all construct an Engine.
"""
from __future__ import annotations

import dataclasses
import importlib
import warnings
from typing import Any, Optional, Tuple, Union

import numpy as np

from repro.core import spans
from repro.graph import structs

#: algo name -> (module, canonical entry point).  Imports are lazy so
#: ``repro.api`` stays importable from inside the algorithm modules.
ALGORITHMS = {
    "hashmin": "repro.algorithms.hashmin",
    "pagerank": "repro.algorithms.pagerank",
    "sssp": "repro.algorithms.sssp",
    "sv": "repro.algorithms.sv",
    "msf": "repro.algorithms.msf",
    "attr_bcast": "repro.algorithms.attr_bcast",
    "gcn": "repro.train.gcn",
}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Execution configuration, orthogonal to any one algorithm.

    ``devices``: None = single-device batched simulation; an int D = the
    1-D sharded mesh; a tuple (H, T) = the hierarchical (host, device)
    mesh.  ``hosts`` additionally makes ``partition()`` place workers
    host-affinely (usually set together with devices=(H, T)).
    """
    backend: str = "dense"          # "dense" | "pallas" channel combine
    layout: str = "padded"          # "padded" | "csr" edge layout
    balance: str = "hash"           # one of graph.partitioner.BALANCES
    devices: Union[int, Tuple[int, int], None] = None
    hosts: Optional[int] = None
    pipeline: bool = False          # double-buffer sharded exchanges
    use_mirroring: bool = True      # Ch_mir for >= tau vertices
    split_factor: float = 1.2       # balance="split" hot-worker factor


@dataclasses.dataclass
class RunResult:
    """Uniform algorithm result: no positional-tuple arity to remember.

    ``state`` is the algorithm's output pytree (labels / pr / dist /
    (labels, total_w, n_edges) / edge attrs / trained params);
    ``history`` is the per-superstep trace when recorded, else None.
    """
    state: Any
    stats: dict
    n_supersteps: int
    history: Any = None

    def load_report(self) -> Optional[dict]:
        """Measured per-worker load telemetry of this run: the
        ``cost_model.straggler_report`` of the summed superstep
        ``per_worker_total`` stats (max/mean imbalance + the worker
        ids carrying the tail) — the signal the resident service's
        elastic repartition trigger watches.  None when the run kept
        no per-worker stats."""
        per_worker = self.stats.get("per_worker_total")
        if per_worker is None:
            parts = [np.asarray(self.stats[k], np.int64)
                     for k in ("per_worker_basic", "per_worker_combined",
                               "per_worker_mirror")
                     if k in self.stats]
            if not parts:
                return None
            per_worker = sum(parts)
        from repro.core import cost_model
        pw = np.asarray(per_worker, np.int64)
        rep = cost_model.straggler_report(pw)
        rep["per_worker_total"] = pw
        rep["top_workers"] = np.argsort(-pw)[:4].tolist()
        return rep


def warn_legacy(name: str, replacement: str) -> None:
    """The one DeprecationWarning every legacy tuple entry point emits
    (``repro.api.Engine`` / the canonical ``run()`` never warns)."""
    warnings.warn(
        f"{name} is deprecated; use {replacement} "
        f"(repro.api.Engine front door) instead",
        DeprecationWarning, stacklevel=3)


def config_of(pg: structs.PartitionedGraph, **overrides) -> EngineConfig:
    """An EngineConfig whose partition-time fields mirror ``pg``."""
    base = dict(layout=pg.layout, balance=pg.balance,
                split_factor=pg.split_factor, hosts=pg.hosts)
    base.update(overrides)
    return EngineConfig(**base)


class Engine:
    """Facade binding an EngineConfig to partitioning + algorithm runs."""

    def __init__(self, config: Optional[EngineConfig] = None, **overrides):
        if config is None:
            config = EngineConfig(**overrides)
        elif overrides:
            config = dataclasses.replace(config, **overrides)
        self.config = config

    def partition(self, g: structs.Graph, M: int,
                  tau: Optional[int] = None, seed: int = 0,
                  perm=None) -> structs.PartitionedGraph:
        cfg = self.config
        return structs.partition(g, M, tau=tau, seed=seed,
                                 layout=cfg.layout, balance=cfg.balance,
                                 split_factor=cfg.split_factor,
                                 hosts=cfg.hosts, perm=perm)

    @spans.traced(spans.ENGINE_RUN)
    def run(self, algo: str, graph, M: Optional[int] = None,
            tau: Optional[int] = None, seed: int = 0,
            **algo_params) -> RunResult:
        """Run ``algo`` on ``graph`` (a PartitionedGraph, or a host Graph
        partitioned on the fly — then ``M`` is required)."""
        if algo not in ALGORITHMS:
            raise ValueError(f"unknown algo {algo!r}; one of "
                             f"{sorted(ALGORITHMS)}")
        if isinstance(graph, structs.PartitionedGraph):
            pg = graph
        else:
            if M is None:
                raise ValueError("partitioning a Graph on the fly needs M")
            pg = self.partition(graph, M, tau=tau, seed=seed)
        mod = importlib.import_module(ALGORITHMS[algo])
        return mod.run(pg, self.config, **algo_params)
