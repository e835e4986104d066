"""Azure-like request streams with a memory footprint per function, for
a node whose resource is memory.

The arrivals, execution, cold-start and eviction times are those of
`bench.traffic.azure` for the same seed, bit for bit. Each function
then gets a footprint ``mem_mb`` from a generator of its own, seeded
from the same seed: log-normal with median ``mem_median_mb`` and
log-sigma ``mem_sigma`` (the Azure Functions 2019 memory
characterisation, Shahrad et al., USENIX ATC '20: about half of the
applications allocate at most about 170 MB, about 90% at most about
400 MB), drawn independently of popularity and execution time, rounded
to whole MB and clipped to ``mem_range_mb`` (OpenWhisk's action memory
limits, 128 to 512 MB).
"""
from __future__ import annotations

import numpy as np

from bench.traffic import azure

# appended to a stream's seed for the footprints' own generator
FOOTPRINT_TAG = 0x6D656D


def footprints(n_functions, *, median_mb, sigma, range_mb, seed):
    """(F,) int32 footprints in MB."""
    rng = np.random.default_rng(seed)
    mb = np.exp(rng.normal(np.log(median_mb), sigma, n_functions))
    return np.clip(np.rint(mb), *range_mb).astype(np.int32)


def stream(config: dict, seed) -> dict:
    """`azure.stream` of ``config`` plus the ``mem_mb`` column; its
    parameters are the ``trace`` block's ``mem_*`` keys."""
    c = config["trace"]
    out = azure.stream(config, seed)
    out["mem_mb"] = footprints(
        config["n_functions"], median_mb=c["mem_median_mb"],
        sigma=c["mem_sigma"], range_mb=tuple(c["mem_range_mb"]),
        seed=[*seed, FOOTPRINT_TAG])
    return out
