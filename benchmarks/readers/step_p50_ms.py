"""Median time of a training step, from the host-clock marks at the
fetches of the loss (each mark closes ``fetch_every`` whole steps)."""

import statistics


def read(obs: dict, args: dict):
    fetches = (obs.get("window") or {}).get("fetches") or []
    if len(fetches) < 2:
        return None
    per_step = [(b[1] - a[1]) / (b[0] - a[0])
                for a, b in zip(fetches, fetches[1:])]
    return statistics.median(per_step) * 1e3
