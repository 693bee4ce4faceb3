"""Finds every piece of a cell by name, so that a configuration, a cell,
a traffic mix or a per-layer metric is added by adding files:

* ``workloads/<cell>.json``: ``config``, ``traffic`` and ``chips``;
* ``configs/<config>.json``: the deployment (graph generator and its
  sizes, the engine's path and worker count), its source, ``reduced``
  and ``assumed``;
* ``traffic/<traffic>.json``: the job (``algo`` and its ``params``), the
  reference it is held to (``ref``), the message counter it reports
  (``counter``) and the limit of each number compared (``limits``);
* ``graphs/<generator>.py``: ``generate(config, seed) -> (n, src, dst)``;
* ``refs/<ref>.py``: ``reference``, ``compare`` and ``from_program``;
* ``metrics/<metric>.py``: ``UNIT`` and ``read(record) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: ModuleType
    ref: ModuleType


def _json(root: Path, kind: str, name: str) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} "
                                f"({path} is missing)")
    return json.loads(path.read_text())


def module(root: Path, kind: str, name: str) -> ModuleType:
    path = root / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} "
                                f"({path} is missing)")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = HERE) -> Cell:
    w = _json(root, "workloads", name)
    cfg = _json(root, "configs", w["config"])
    tr = _json(root, "traffic", w["traffic"])
    return Cell(name=name, chips=int(w["chips"]), config=cfg, traffic=tr,
                generator=module(root, "graphs", cfg["generator"]),
                ref=module(root, "refs", tr["ref"]))


def metric_readers(root: Path = HERE) -> dict:
    """Every per-layer metric reader, by metric name."""
    return {p.stem: module(root, "metrics", p.stem)
            for p in sorted((root / "metrics").glob("*.py"))}
