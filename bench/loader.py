"""Find a cell's configuration, traffic mix and per-layer metric readers by
name.

``BENCHMARK.json`` names them; each lives in a file of its own:

* ``bench/configs/<config>.json``: the model, the architecture file it is
  measured with (``arch``), its published sizes, the engine settings,
  replicas, router and admission, weights and the check;
* ``bench/archs/<arch>.py``: an architecture, with
  ``program_want(published, arch)``, ``make(published, arch, key)``,
  ``logits_at(published, arch, w, tokens, rows, rnd)`` and
  ``dims(published)`` (see ``bench/archs/dense.py``);
* ``bench/traffic/<mix>.json``: the arrival process, lengths, limits;
* ``bench/metrics/<metric>.py``: a reader with ``read(run) -> float | None``.

Adding a cell adds files and entries; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import types

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class BenchError(RuntimeError):
    """The benchmark's files are inconsistent or a cell cannot run."""


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise BenchError(f"{what} {name!r} is not a valid name")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise BenchError(f"unit {unit!r} is not a valid unit")
    return unit


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"{path} is missing")
    with path.open() as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict                  # metric name -> module with read(run)
    arch: types.ModuleType         # bench/archs/<config["arch"]>.py


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def _load_module(kind: str, name: str, path: pathlib.Path,
                 api: tuple[str, ...]) -> types.ModuleType:
    """The module at ``path``, which must define the callables ``api``."""
    if not path.is_file():
        raise BenchError(f"no {kind} file {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in api if not callable(getattr(mod, f, None))]
    if missing:
        raise BenchError(f"{path} defines no {', '.join(missing)}")
    return mod


def load_reader(name: str, bench: pathlib.Path = BENCH):
    path = bench / "metrics" / f"{check_name(name, 'metric')}.py"
    return _load_module("metric", name, path, ("read",))


ARCH_API = ("program_want", "make", "logits_at", "dims")


def load_arch(name: str, bench: pathlib.Path = BENCH):
    path = bench / "archs" / f"{check_name(name, 'architecture')}.py"
    return _load_module("arch", name, path, ARCH_API)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench: pathlib.Path | None = None) -> Cell:
    """Resolve workload ``name`` of ``root/BENCHMARK.json``."""
    bench = bench or root / "bench"
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    for key in ("name", "config", "traffic"):
        check_name(w[key], key)
    config = _json(bench / "configs" / f"{w['config']}.json")
    if "arch" not in config:
        raise BenchError(f"configuration {w['config']!r} names no "
                         f"architecture file (key 'arch')")
    arch = load_arch(config["arch"], bench)
    traffic = _json(bench / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    layer = [m for m in spec["per_layer"] if _applies(m, name)]
    for m in e2e + layer:
        check_name(m["name"], "metric")
        check_unit(m["unit"])
    readers = {m["name"]: load_reader(m["name"], bench) for m in layer}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer,
                readers=readers, arch=arch)
