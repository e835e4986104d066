"""`paper_edge.fig5` reads the same work, builds the same spec and
meets the same reference as before a configuration could name its own
sampler, program options, reference and statistics.

``data/fig5_parent.json`` holds what `readings` gave on the commit
before that change: this file was copied into a checkout of that commit
and `readings` run there, on the host. Every reading here has to equal
it bit for bit. Host only: nothing runs on a device."""
import hashlib
import json
import os
from dataclasses import fields

import pytest

from bench import check
from bench.cell import Cell

PARENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "fig5_parent.json")
# the arguments the harness always gives `ExperimentSpec`
FIXED = ("traces", "policies", "capacities", "queue_cap", "stream", "prior",
         "deadlines", "cluster")
HEAD = 300
REQUEST_COLUMNS = ("fn_id", "arrival", "exec_time")
LANES = (("esff", 8, 0), ("esff_h", 20, 0), ("sff", 32, 0),
         ("openwhisk", 12, 0), ("faascache", 16, 0), ("openwhisk_v2", 24, 0))


def digest(a) -> str:
    """sha256 of an array's type, shape and bytes."""
    h = hashlib.sha256(f"{a.dtype.str}{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()


def plain(v):
    """A spec field's value as JSON: a trace as its label and the
    digests of its columns."""
    if isinstance(v, (tuple, list)):
        return [plain(x) for x in v]
    if hasattr(v, "arrays_in"):
        return [v.label, {k: digest(a) for k, a in v.arrays_in}]
    return v


def readings() -> dict:
    """The pool's arrays, the spec of unit 0 and the reference's
    statistics of a few lanes over the first `HEAD` requests of unit
    0."""
    cell = Cell.load("paper_edge.fig5")
    pool = cell.pool()
    spec = cell.spec(pool[0])
    default = type(spec)()
    head = {k: (a[:HEAD] if k in REQUEST_COLUMNS else a)
            for k, a in pool[0][0].items()}
    return {
        "arrays": {f"{u}.{k}.{col}": digest(a)
                   for u, unit in enumerate(pool)
                   for k, stream in enumerate(unit)
                   for col, a in sorted(stream.items())},
        "spec": {"set": sorted(f.name for f in fields(spec)
                               if getattr(spec, f.name)
                               != getattr(default, f.name)),
                 "fixed": {n: plain(getattr(spec, n)) for n in FIXED}},
        "reference": {".".join(map(str, lane)):
                      check.reference(cell, [head], lane)
                      for lane in LANES},
    }


@pytest.fixture(scope="module")
def now():
    return readings()


@pytest.mark.parametrize("part", ["arrays", "spec", "reference"])
def test_fig5_reads_as_on_the_parent(now, part):
    with open(PARENT) as f:
        parent = json.load(f)
    assert now[part] == parent[part]
