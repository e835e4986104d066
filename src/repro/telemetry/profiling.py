"""Run-provenance metadata: what produced a benchmark row (spec hash,
backend, device, chunking, jit cache state). It rides the registries
that already exist — ``jit_cache_sizes()`` on the runners for cache
state. Where wall time goes is read from the runner's host spans in a
`jax.profiler` trace (docs/observability.md, "Performance spans")."""
from __future__ import annotations

import hashlib
import json
from typing import Dict

import jax


def spec_hash(spec) -> str:
    """Stable short hash of an ExperimentSpec's semantic content."""
    try:
        payload = spec.meta
    except Exception:
        payload = {k: v for k, v in vars(spec).items()
                   if not k.startswith("_")}
    blob = json.dumps(payload, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def provenance(spec=None, **extra) -> Dict[str, object]:
    """Run-provenance dict folded into BENCH rows and result meta."""
    from repro.api.runner import jit_cache_sizes
    dev = jax.devices()[0]
    out: Dict[str, object] = dict(
        backend=dev.platform,
        device=getattr(dev, "device_kind", str(dev)),
        n_devices=jax.device_count(),
        jax_version=jax.__version__,
        x64=bool(jax.config.jax_enable_x64),
        jit_cache_sizes=jit_cache_sizes(),
    )
    if spec is not None:
        out["spec_hash"] = spec_hash(spec)
        out["lane_chunk"] = getattr(spec, "lane_chunk", None)
        out["trace_events"] = bool(getattr(spec, "trace_events",
                                           False))
    out.update(extra)
    return out
