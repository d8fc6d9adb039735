"""Cells are found by name: a new cell needs new files and entries only;
traffic gives every seed the same work in another order."""
import importlib.util
import json
import os
import shutil
import sys

import numpy as np

from bench import cell as C
from bench import traffic


def _copy_checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(C.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(C.ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    spec = importlib.util.spec_from_file_location("cell_copy",
                                                  root / "bench" / "cell.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["cell_copy"] = mod  # dataclasses look their module up
    try:
        spec.loader.exec_module(mod)
    finally:
        del sys.modules["cell_copy"]
    return root, mod


def test_a_cell_from_new_files_alone(tmp_path):
    root, mod = _copy_checkout(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    mix = json.loads((root / "bench/traffic/chat.json").read_text())
    mix["rate_per_s"] = 0.1
    (root / "bench/traffic/trickle.json").write_text(json.dumps(mix))
    (root / "bench/metrics/trickle_probe.py").write_text(
        "def read(r):\n    return 42.0\n")
    bench = json.loads(before[root / "BENCHMARK.json"])
    bench["workloads"].append({"name": "phi4-trickle",
                               "config": "phi4-mini-ternary-kv8",
                               "traffic": "trickle", "chips": 1,
                               "why": "a test cell"})
    bench["end_to_end"][0]["workloads"].append("phi4-trickle")
    bench["per_layer"].append({"name": "trickle_probe", "unit": "x",
                               "better": "lower", "source": "host_clock",
                               "layer": "engine", "moves": "itl_p95_ms",
                               "workloads": ["phi4-trickle"]})
    new_bench = root / "BENCHMARK.next.json"
    new_bench.write_text(json.dumps(bench))
    cell = mod.load("phi4-trickle", str(new_bench))
    assert cell.mix["rate_per_s"] == 0.1
    assert [m["name"] for m in cell.per_layer] == ["trickle_probe"]
    assert "itl_p95_ms" in [m["name"] for m in cell.end_to_end]
    assert mod.metric_reader("trickle_probe")(None) == 42.0
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file that was there changed


def test_every_benchmark_metric_has_a_reader():
    bench = C._load_json(os.path.join(C.ROOT, "BENCHMARK.json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(C.metric_reader(m["name"])), m["name"]
    for w in bench["workloads"]:
        cell = C.load(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def _shape(specs):
    return [(len(x.prompt), x.max_new, x.due_s) for x in specs]


def test_seeds_share_the_schedule():
    """A mix with an ``order_seed`` gives every seed one schedule."""
    m = C._load_json(os.path.join(C.BENCH, "traffic", "longdoc.json"))
    runs = [traffic.generate(m, s, 40.0, 1000) for s in (1, 2**33 + 1)]
    assert _shape(runs[0]) == _shape(runs[1])
    assert runs[0][0].prompt != runs[1][0].prompt
    lim = m["engine"]["max_len"] - 2
    assert all(len(x.prompt) + x.max_new <= lim for x in runs[0])
    lengths = [len(x.prompt) for x in runs[0]]
    assert sorted(lengths) != lengths


def test_seeds_reorder_the_same_work():
    """Open loop: each seed serves the same lengths and gaps, before the
    window and inside it, in its own order."""
    m = C._load_json(os.path.join(C.BENCH, "traffic", "chat.json"))
    seconds, warm = 40.0, m["warm_s"]
    runs = [traffic.generate(m, s, seconds, 1000) for s in (1, 2**33 + 1)]
    lim = m["engine"]["max_len"] - 2
    for r in runs:
        assert all(len(x.prompt) + x.max_new <= lim for x in r)
        due = [x.due_s for x in r]
        assert due == sorted(due) and -warm < due[0] and due[-1] < seconds
        assert sum(d >= 0 for d in due) == int(m["rate_per_s"] * seconds)
        assert sum(d < 0 for d in due) == int(m["rate_per_s"] * warm)
    assert _shape(runs[0]) != _shape(runs[1])
    for start, inside in ((-warm, lambda d: d < 0), (0.0, lambda d: d >= 0)):
        a, b = ([x for x in r if inside(x.due_s)] for r in runs)
        for size in (lambda x: len(x.prompt), lambda x: x.max_new):
            assert sorted(map(size, a)) == sorted(map(size, b))
        gaps = [sorted(np.diff([start] + [x.due_s for x in p])) for p in (a, b)]
        assert np.allclose(gaps[0], gaps[1])
