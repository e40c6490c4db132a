"""The one traffic generator: a mix file's parameters and a seed in,
every input of a run out.

A mix is a JSON file under ``mixes/`` whose ``loop`` names how it is
driven (``loops.py``):

* ``batch_fit``, a closed loop: one set of the configuration's ``n``
  points, fitted again and again.

Points come from the configuration's ``data`` block (``datagen.py``),
drawn from its ``data_seed``: every run fits the same points, so every
run does the same work.  The run seed draws the order in which each
shard's points are handed on.  The same seed gives the same inputs,
whatever the machine.
"""
from __future__ import annotations

import numpy as np

from bench import datagen


def batch_fit(config: dict, mix: dict, seed: int) -> dict:
    """Inputs of one ``batch_fit`` run: the ``n`` points every fit takes,
    in Morton-order blocks of one shard each, every block in an order
    drawn from ``seed``."""
    data, n = config["data"], int(config["n"])
    pts = datagen.draw(data, n, np.random.default_rng(int(data["data_seed"])))
    rng = np.random.default_rng(seed)
    blocks = np.array_split(np.arange(n), int(config["shards"]))
    order = np.concatenate([rng.permutation(b) for b in blocks])
    return {"points": pts[order].astype(np.float32)}
