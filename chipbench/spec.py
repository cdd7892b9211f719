"""Find every piece of a cell by the name ``BENCHMARK.json`` gives it.

    configuration  BENCHMARK.json ``configs[].file``   (a JSON file)
    architecture   chipbench/architectures/<architecture>.py, named by
                   the configuration's ``"architecture"`` key: its
                   weights, float32 reference and counts (``INTERFACE``)
    traffic mix    chipbench/traffic/<traffic>.json
    metric         chipbench/metrics/<metric name>.py  (defines ``read``),
                   else chipbench/metrics/<name less its last part>.py,
                   so ``engine.decode_step_ms.sat`` and ``.r80`` share
                   ``engine.decode_step_ms.py``
    peaks          chipbench/peaks.json, keyed by ``device_kind``

Nothing here names a configuration, architecture, mix or metric: a new
one is a new file and, but for an architecture, an entry in
``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent

# what every architecture file defines: the harness calls these by name
INTERFACE = ("shapes", "make",
             "logits", "served_gaps", "pad_length", "pad_rows",
             "weight_bytes", "kv_bytes_per_position", "decode_flops",
             "decode_bytes", "prefill_flops", "paged_decode_flops",
             "paged_decode_bytes")


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    kind: str                       # 'end_to_end' | 'per_layer'
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    arch: ModuleType                # chipbench/architectures/<name>.py
    mix: dict
    metrics: List[Metric]           # end-to-end then per-layer


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, prefix: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, name: str) -> Callable:
    folder = root / "chipbench" / "metrics"
    path = folder / f"{name}.py"
    if not path.exists() and "." in name:
        path = folder / f"{name.rsplit('.', 1)[0]}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} in {folder}")
    return _load_module(path, "chipbench_metric").read


def load_architecture(root: Path, name: str) -> ModuleType:
    path = root / "chipbench" / "architectures" / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no architecture file {path}")
    mod = _load_module(path, "chipbench_arch")
    missing = [n for n in INTERFACE if not callable(getattr(mod, n, None))]
    if missing:
        raise AttributeError(f"{path} does not define {missing}")
    return mod


def load_cell(root: Path, workload: str) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config_file = root / configs[w["config"]]["file"]
    config = load_json(config_file)
    if "architecture" not in config:
        raise ValueError(f"{config_file} names no \"architecture\"")
    mix = load_json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            cells_of = m.get("workloads")
            if cells_of is not None and workload not in cells_of:
                continue
            metrics.append(Metric(
                name=m["name"], unit=m["unit"], kind=kind,
                read=load_reader(root, m["name"])))
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                arch=load_architecture(root, config["architecture"]),
                mix=mix, metrics=metrics)


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table ({sorted(table)})")
    return table[device_kind]
