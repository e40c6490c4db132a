"""Toy sizes of the benchmark's cells, for tests on the CPU: the cells'
own entries, configurations and mixes, cut so that one run takes
seconds."""
import copy
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


def spreader(name="spreader.fit", shards=4, n=4 * 4096):
    """(cell, config, mix, end-to-end, per-layer) of the seed-spreader
    cell ``name`` at ``n`` points over ``shards``."""
    cell, config, mix, e2e, layer = harness.cell_parts(harness.load_doc(), name)
    config = copy.deepcopy(config)
    config.update(shards=shards, n=n)
    return cell, config, mix, e2e, layer
