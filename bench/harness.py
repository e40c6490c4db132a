"""From ``BENCHMARK.json`` and a cell's name to its result line.

Everything about a cell is found by name: its configuration file, its
mix (``mixes/<traffic>.json``), its end-to-end metrics and the per-layer
metrics that list it, each read by ``metrics/<name>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import pathlib

from bench import loops

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_doc(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(doc: dict, name: str, root: pathlib.Path = ROOT) -> tuple:
    """(cell, config, mix, end-to-end metrics, per-layer metrics)."""
    cells = {w["name"]: w for w in doc["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in doc["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "mixes" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in doc["end_to_end"] if name in m.get("workloads", [name])]
    layer = [m for m in doc["per_layer"] if name in m.get("workloads", [name])]
    return cell, config, mix, e2e, layer


def reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int, run: loops.Run) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips),
            "memory_peak_bytes": run.info["peak_bytes"]}
    if run.trace is not None:
        info["busy_s"] = run.trace["busy_s"]
        info["window_s"] = run.trace["window_s"]
    return info


def run_cell(doc: dict, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: pathlib.Path = ROOT) -> tuple:
    """Drive one run of a cell.  Returns (result dict, notes to print
    before it)."""
    cell, config, mix, e2e, layer = cell_parts(doc, name, root)
    run = loops.LOOPS[mix["loop"]](config, mix, seed, seconds, trace, t_start)
    return result(run, cell, e2e, layer, root), run.notes


def result(run: loops.Run, cell: dict, e2e: list, layer: list,
           root: pathlib.Path = ROOT) -> dict:
    metrics = {}
    if run.trace is None:
        for m in e2e:
            v = run.values.get(m["name"])
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in layer:
            v = reader(m["name"], root)(run)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {
        "correct": run.correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device_info(int(cell["chips"]), run),
    }
    if run.trace is not None and run.trace.get("breakdown"):
        out["breakdown"] = run.trace["breakdown"]
    out["compared"] = run.compared
    return out


def compared_lines(out: dict) -> list:
    """One line per number compared, with its limit."""
    return [f"compared {k}: {c['value']} (limit {c['limit']})"
            for k, c in out["compared"].items()]
