"""A number the driver already holds: ``args.path`` is a dotted path
into the observations, ``args.scale`` multiplies it."""


def read(obs: dict, args: dict):
    node = obs
    for key in args["path"].split("."):
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    if node is None:
        return None
    return float(node) * args.get("scale", 1.0)
