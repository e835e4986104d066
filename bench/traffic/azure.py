"""The benchmark's request-stream generator: an Azure-Functions-like
synthetic trace (paper §VI-A; Zhang et al., SOSP '21).

A copy of the sampler the program ships in `repro.traces.generator`
(`_sample_azure` and the columnar `synth_azure_arrays`), kept here so
that the traffic the benchmark offers cannot change with the program.
For the same parameters it returns the same arrays bit for bit.

* function popularity ~ Zipf(``zipf_a``);
* per-function base execution time ~ log-normal(``exec_median``,
  ``exec_sigma``), per-request jitter ~ log-normal(0, ``jitter_sigma``),
  quantised to 1 ms with a 1 ms floor;
* arrivals: per-minute counts from a diurnal profile times a log-normal
  burst process, uniform within each minute;
* cold-start and eviction times ~ U[``cold_range``] per function.

`stream` is the entry point `bench.cell.Cell.stream` calls for a
configuration whose ``trace`` block names no other sampler.
"""
from __future__ import annotations

import numpy as np


def sample(n_functions, n_requests, *, utilization, capacity_ref, zipf_a,
           exec_median, exec_sigma, jitter_sigma, cold_range, burst_frac,
           diurnal_amp, seed):
    """Arrival-sorted columns ``fn_id``, ``arrival``, ``exec_time`` and
    the catalogue ``cold_start``, ``evict``. ``seed`` is anything
    `numpy.random.default_rng` takes, such as a list of integers."""
    rng = np.random.default_rng(seed)

    pop = 1.0 / np.arange(1, n_functions + 1) ** zipf_a
    pop /= pop.sum()
    base_exec = np.exp(rng.normal(np.log(exec_median), exec_sigma,
                                  n_functions))
    base_exec = np.clip(base_exec, 1e-3, 120.0)
    cold = rng.uniform(*cold_range, n_functions)
    evict = rng.uniform(*cold_range, n_functions)

    counts = rng.multinomial(n_requests, pop)

    total_exec = float((counts * base_exec).sum())
    duration = total_exec / (utilization * capacity_ref)

    day = 86_400.0
    n_min = max(int(np.ceil(duration / 60.0)), 1)
    minute_t = (np.arange(n_min) + 0.5) * 60.0
    fn_col, arr_col, exe_col = [], [], []
    for j in range(n_functions):
        n_j = int(counts[j])
        if n_j == 0:
            continue
        phase = rng.uniform(0, 2 * np.pi)
        diurnal = 1 + diurnal_amp * np.sin(2 * np.pi * minute_t / day + phase)
        sigma_b = np.log(10.0) * burst_frac * 2
        bursts = np.exp(rng.normal(0, sigma_b, n_min))
        weights = np.clip(diurnal, 0.05, None) * bursts
        weights /= weights.sum()
        per_min = rng.multinomial(n_j, weights)
        nz = np.nonzero(per_min)[0]
        t = np.concatenate([
            (m + rng.uniform(0, 1, per_min[m])) * 60.0 for m in nz
        ]) if len(nz) else np.empty(0)
        ex = base_exec[j] * np.exp(rng.normal(0, jitter_sigma, n_j))
        ex = np.maximum(np.round(ex, 3), 1e-3)
        fn_col.append(np.full(n_j, j, np.int32))
        arr_col.append(t)
        exe_col.append(ex)

    fn_ids = np.concatenate(fn_col)
    arrivals = np.concatenate(arr_col)
    execs = np.concatenate(exe_col)
    order = np.argsort(arrivals, kind="stable")
    return dict(fn_id=fn_ids[order].astype(np.int32),
                arrival=arrivals[order].astype(np.float64),
                exec_time=execs[order].astype(np.float64),
                cold_start=np.asarray(cold, np.float64),
                evict=np.asarray(evict, np.float64))


def stream(config: dict, seed) -> dict:
    """The stream of ``config``: its ``trace`` block holds `sample`'s
    parameters, with times in seconds."""
    c = config["trace"]
    return sample(config["n_functions"], config["n_requests"],
                  utilization=c["utilization"],
                  capacity_ref=c["capacity_ref"], zipf_a=c["zipf_a"],
                  exec_median=c["exec_median_s"], exec_sigma=c["exec_sigma"],
                  jitter_sigma=c["jitter_sigma"],
                  cold_range=tuple(c["cold_range_s"]),
                  burst_frac=c["burst_frac"], diurnal_amp=c["diurnal_amp"],
                  seed=seed)
