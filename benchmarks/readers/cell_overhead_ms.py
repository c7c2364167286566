"""What the notebook and the control plane add to the window's cell:
the coordinator's latency stages (``comm.lat``) other than the
execution itself."""


def read(obs: dict, args: dict):
    stages = obs.get("cell_lat") or {}
    if "execute" not in stages:
        return None
    return 1e3 * sum(v for k, v in stages.items() if k != "execute")
