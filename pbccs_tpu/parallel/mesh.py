"""Mesh construction and sharding specs for the ZMW batch pipeline.

TPU-native replacement for the reference's thread-pool scheduling
(reference include/pacbio/ccs/WorkQueue.h:53-217): instead of handing one
ZMW to one thread, batches of bucketed ZMWs are laid out on a 2-D device
mesh ('zmw' x 'read') and every polish round is one jitted program; XLA
partitions it and inserts the read-axis all-reduce for score totals.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ZMW_AXIS = "zmw"
READ_AXIS = "read"


def make_zmw_mesh(n_zmw: int | None = None, n_read: int = 1,
                  devices: Sequence[jax.Device] | None = None) -> Mesh:
    """A ('zmw', 'read') mesh over the available devices.

    By default all devices go to the 'zmw' (data-parallel) axis; pass
    n_read > 1 to dedicate a read-parallel subaxis (useful for high-pass
    ZMWs where R is large and Z is small).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n_zmw is None:
        if n % n_read != 0:
            raise ValueError(f"{n} devices not divisible by n_read={n_read}")
        n_zmw = n // n_read
    if n_zmw * n_read > n:
        raise ValueError(f"mesh {n_zmw}x{n_read} needs more than {n} devices")
    grid = np.asarray(devices[: n_zmw * n_read]).reshape(n_zmw, n_read)
    return Mesh(grid, (ZMW_AXIS, READ_AXIS))


def zmw_spec(ndim: int, read_axis: int | None = None) -> P:
    """PartitionSpec for an array with a leading ZMW axis and (optionally) a
    read axis at position `read_axis`; other axes replicated."""
    parts: list = [ZMW_AXIS] + [None] * (ndim - 1)
    if read_axis is not None:
        parts[read_axis] = READ_AXIS
    return P(*parts)


def shard_batch(mesh: Mesh, tree, read_axis_of=lambda path: None):
    """Device_put a pytree of batch arrays with ZMW-sharded leading axes."""
    def place(x):
        x = np.asarray(x)
        spec = zmw_spec(x.ndim)
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(place, tree)


def pad_to(n: int, quantum: int) -> int:
    """Round n up to a multiple of `quantum` (>= quantum)."""
    return max(quantum, int(math.ceil(n / quantum)) * quantum)
