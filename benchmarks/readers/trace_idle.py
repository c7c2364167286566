"""Share of the traced window in which no operation ran on the device
(averaged over the chips used)."""


def read(obs: dict, args: dict):
    trace = obs.get("trace") or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
