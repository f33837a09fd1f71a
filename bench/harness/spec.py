"""What a cell is, found by name: ``BENCHMARK.json`` names each cell's
configuration and traffic mix, and the harness reads everything else
from files of their own.

    <root>/BENCHMARK.json            cells, metrics, bounds
    <file of the config>             the deployment (``configs`` entry)
    bench/traffic/<traffic>.json     the traffic mix
    bench/limits/<workload>.json     the limits of the correctness check
    bench/metrics/<metric>.py        one per-layer metric: ``read(run)``
    bench/reference/<name>.py        the plain reference a config names

A later cell or metric is a new file plus a new entry in
``BENCHMARK.json``; no file the harness reads needs an edit.

The client model is the reference's alone.  The configuration names it
(``"reference": "<name>"``) and gives its class count
(``model.classes``); the harness calls only these functions of
``bench/reference/<name>.py``:

  init(key, num_classes) -> tree
      the per-device parameters, from ``key``.  Anything every device
      shares (a frozen backbone) stays inside the module, derived from
      the key it is given; the harness broadcasts to the devices, and
      compares, only what ``init`` returns.  Leaves are keyed by their
      path in the tree (``replay.flat_leaves``), so the tree's names
      must match the program's parameters.
  cast(tree, dtype) -> tree
  stack(devices, dtype, n_max=None) -> dict
      the client's device-major data layout, from the device objects
      the run captured, padded to ``n_max`` rows (default: the largest
      device's): ``x``, ``y``, ``labeled``, ``valid``, ``true_y``.
      Only floating-point inputs take ``dtype``; integer inputs (token
      ids) stay integers.
  train(params, x, y, labeled, valid, lane_keys, update, *, iters,
        batch, lr, dtype) -> params
  accuracies(params, x, true_y, valid, *, dtype) -> (devices,)
  pair_divergence(h0, xi, ni, xj, nj, keys, *, tau, T, batch, lr,
                  dtype) -> (lanes,)
  mix_targets(params, alpha, psi, dtype) -> params
  costs(sim) -> dict
      operations of ``train_sample`` (one training sample),
      ``eval_sample`` (one measurement forward) and ``pair`` (one
      Algorithm-1 estimate, training and evaluation), and
      ``transfer_width``: V, the per-device parameters the combine
      moves; ``sim`` is the run's ``SimConfig`` as a dict.

A new client is new files only: a config, a reference, traffic, limits
and metric readers.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = "bench"


@dataclasses.dataclass
class Cell:
    root: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _for_cell(metrics: List[dict], name: str) -> List[dict]:
    return [m for m in metrics
            if "workloads" not in m or name in m["workloads"]]


def load_cell(root: str, workload: str) -> Cell:
    """The cell named ``workload``; KeyError if BENCHMARK.json has none."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"available: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(root, BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))
    limits = _read_json(os.path.join(root, BENCH_DIR, "limits",
                                     workload + ".json"))
    return Cell(root=root, workload=w, config=config, traffic=traffic,
                limits=limits,
                end_to_end=_for_cell(bench["end_to_end"], workload),
                per_layer=_for_cell(bench["per_layer"], workload))


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_readers(cell: Cell) -> Dict[str, Callable]:
    """``bench/metrics/<name>.py``'s ``read`` for each per-layer metric of
    the cell, by name."""
    out = {}
    for m in cell.per_layer:
        path = os.path.join(cell.root, BENCH_DIR, "metrics",
                            m["name"] + ".py")
        out[m["name"]] = _load_module(path, "bench_metric_" +
                                      m["name"].replace(".", "_")).read
    return out


def reference_module(cell: Cell, name: Optional[str] = None):
    """The plain reference the configuration names."""
    name = name or cell.config["reference"]
    return _load_module(os.path.join(cell.root, BENCH_DIR, "reference",
                                     name + ".py"), "bench_ref_" + name)


def sim_config_kwargs(cell: Cell) -> dict:
    """The ``SimConfig`` fields the deployment's file sets, then those
    the traffic mix's file sets; other keys describe and are skipped."""
    import dataclasses
    from repro.sim.engine import SimConfig
    fields = {f.name for f in dataclasses.fields(SimConfig)}
    kw = {k: v for k, v in cell.config.items() if k in fields}
    kw.update({k: v for k, v in cell.traffic.items() if k in fields})
    if "tick_periods" in kw:
        kw["tick_periods"] = tuple(kw["tick_periods"])
    return kw
