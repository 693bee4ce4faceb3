"""Partitioner layer: the busiest worker's message count over the mean
worker's, from the exact ``per_worker_*`` counters of the first window
job (what ``RunResult.load_report()["max_over_mean"]`` reports)."""
UNIT = "ratio"
PARTS = ("per_worker_basic", "per_worker_combined", "per_worker_mirror")


def read(rec):
    import numpy as np
    stats = rec["jobs"][0]["stats"]
    if "per_worker_total" in stats:
        load = np.asarray(stats["per_worker_total"], np.float64)
    else:
        parts = [np.asarray(stats[k], np.float64) for k in PARTS
                 if k in stats]
        if not parts:
            return None
        load = sum(parts)
    mean = load.mean()
    return float(load.max() / mean) if mean > 0 else None
