"""What the benchmark knows of the program's device work: how to recognise
its executables and kernels in a trace, and the bytes a gather moves.

The names are those the chip's trace shows for the program as it stands (see
``PERF.md``); a program change that renames them leaves the metrics that use
them silent, never wrong.
"""
from __future__ import annotations

import numpy as np

# executable (``XLA Modules`` line) of the beam search, and the exact
# scorer's fused gather kernel (``XLA Ops`` line), named after the jitted
# function that holds its ``pallas_call`` (``kernels/gather_distance.py``);
# ``gather_bytes`` counts that scorer's bytes only
BEAM_SEARCH_MODULE = "jit_beam_search"
GATHER_KERNEL_OPS = ("gather_distance",)


def is_beam_search(name: str) -> bool:
    return name == BEAM_SEARCH_MODULE


def is_gather_kernel(name: str) -> bool:
    return name.split(".", 1)[0] in GATHER_KERNEL_OPS


def gather_bytes(n_comps: int, config: dict) -> int:
    """Bytes the algorithm needs to score ``n_comps`` rows: each row at the
    configuration's width and dtype, plus its int32 id."""
    row = config["d"] * np.dtype(config["dtype"]).itemsize
    return n_comps * (row + 4)
