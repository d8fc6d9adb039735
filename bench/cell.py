"""Find a cell's pieces by name: ``BENCHMARK.json`` names the cell, its
configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<mix>.json``) and the per-layer metrics whose readers
(``bench/metrics/<metric>.py``) it reports.  Adding a cell, a mix, a
configuration or a metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file
    mix: dict  # the traffic file
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load(name: str, benchmark: str = os.path.join(ROOT, "BENCHMARK.json")) -> Cell:
    bench = _load_json(benchmark)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    mix = _load_json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, w["chips"], config, mix, e2e, layer)


def metric_reader(name: str) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_model(config: dict) -> Dict:
    """The sizes the plain reference needs, from the configuration file
    alone (the reference reads nothing of the program)."""
    c = config
    if c["partial_rotary_factor"] != 1.0 or c["rope_scaling"] is not None:
        raise ValueError("the plain reference rotates every dimension, unscaled")
    pad = c["vocab_pad_to"]
    return {
        "d_model": c["hidden_size"],
        "n_layers": c["num_hidden_layers"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c["head_dim"],
        "d_ff": c["intermediate_size"],
        "padded_vocab": -(-c["vocab_size"] // pad) * pad,
        "rope_theta": c["rope_theta"],
        "norm_eps": c["rms_norm_eps"],
        "dtype": c["torch_dtype"],
        "formats": c["quant"]["formats"],
    }
