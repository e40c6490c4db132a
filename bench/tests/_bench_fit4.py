"""Toy-size runs of ``spreader.fit4`` on four virtual CPU devices, in a
process of its own: the device count has to be set before JAX starts.

    python3 bench/tests/_bench_fit4.py

Drives the cell's own loop (``loops.run_batch_fit``) through the ``jit``
backend at 4 x 4,096 points and prints one line per check,
``<check> <value>``, read by ``test_bench_fit4.py``:

* ``sound correct=<bool>``: the run against the plain reference;
* ``lane_stats equal=<bool>``: the per-lane lists of the window's last
  ``ddc.run`` span against ``local_phase_stats`` on each padded shard;
* ``truncated sound=<n> tiny=<n>``: contours cut at ``max_verts`` in the
  sound run and in a fit with ``max_verts`` 4;
* ``no_exchange correct=<bool>``: the run with a fault planted, each
  lane keeping its local slot map with the butterfly rounds skipped.
"""
import copy
import os
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from _bench_toy import spreader  # noqa: E402

from bench import loops, traffic  # noqa: E402

SEED = 2**31 + 515151
STATS = ("sweeps", "doubling_steps", "tile_pairs_active", "tile_pairs",
         "dense_fallback")


def last_run_span():
    from repro import obs

    return [s for s in obs.spans() if s.name == "ddc.run"][-1]


def lane_stats_equal(config, mix) -> bool:
    """The last ``ddc.run`` span's lists against ``local_phase_stats`` run
    alone on each shard, padded as the backend pads it."""
    from repro.core import ddc

    span = last_run_span()
    pts = traffic.batch_fit(config, mix, SEED)["points"]
    blocks = np.array_split(pts, int(config["shards"]))
    cap = max(16, 1 << (max(map(len, blocks)) - 1).bit_length())
    cfg = loops.build_model(config).config.core()
    for lane, block in enumerate(blocks):
        padded = np.zeros((cap, 2), np.float32)
        padded[:len(block)] = block
        mask = np.arange(cap) < len(block)
        _, _, st = ddc.local_phase_stats(jnp.asarray(padded), jnp.asarray(mask), cfg)
        for key in STATS:
            if span.attrs[key][lane] != getattr(st, key).item():
                print(f"lane {lane} {key}: {span.attrs[key][lane]} != "
                      f"{getattr(st, key).item()}", flush=True)
                return False
    return True


def plant_no_exchange() -> None:
    from repro.core import ddc

    def local_only(cs, cfg, axis, meter=None):
        own = jnp.arange(cfg.max_clusters, dtype=jnp.int32)
        return cs, jnp.where(cs.valid, own, -1), jnp.asarray(0, jnp.int32)

    ddc.merge_async = local_only


def main() -> None:
    _, config, mix, _, _ = spreader("spreader.fit4", shards=4, n=4 * 4096)
    run = loops.run_batch_fit(config, mix, SEED, 0.5, False, time.perf_counter())
    print(run.compared, run.notes, flush=True)
    print(f"sound correct={run.correct}", flush=True)
    print(f"lane_stats equal={lane_stats_equal(config, mix)}", flush=True)
    sound_cut = last_run_span().attrs["truncated"]
    tiny = copy.deepcopy(config)
    tiny["ddc"]["max_verts"] = 4
    loops.build_model(tiny).fit(traffic.batch_fit(tiny, mix, SEED)["points"]).labels_
    print(f"truncated sound={sound_cut} tiny={last_run_span().attrs['truncated']}",
          flush=True)
    plant_no_exchange()
    run = loops.run_batch_fit(config, mix, SEED, 0.5, False, time.perf_counter())
    print(run.compared, flush=True)
    print(f"no_exchange correct={run.correct}", flush=True)


if __name__ == "__main__":
    main()
