"""The controls: the plain reference with one stated guarantee broken.
Each has to come out as not correct, where the program comes out as
correct.

- EC cells: parity computed as if data chunk k-1 were all zeros -- a
  code that does not protect that chunk.
- Placement cells: up sets computed with every reweight ignored -- a
  stale map, in which an OSD marked out still holds shards.
"""

from __future__ import annotations

import importlib

import numpy as np


def ec_parity(plugin: str):
    ref = importlib.import_module(f"benchmark.reference.{plugin}")

    def parity(data, profile):
        data = np.array(data)
        data[:, -1] = 0
        return ref.parity(data, profile)
    return parity


def crush_pg_to_up():
    from benchmark.reference import crush

    def pg_to_up(m, pool, weight):
        return crush.pg_to_up(m, pool, np.full(len(weight), 0x10000))
    return pg_to_up


def for_cell(cell):
    if hasattr(cell, "plugin"):
        return ec_parity(cell.plugin)
    return crush_pg_to_up()
