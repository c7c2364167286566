"""The sum of numbers the driver already holds: ``args.paths`` are
dotted paths into the observations (``readers/value.py`` reads each);
nothing where any of them is missing."""

from benchmarks.readers import value


def read(obs: dict, args: dict):
    parts = [value.read(obs, {"path": p}) for p in args["paths"]]
    return None if None in parts else sum(parts)
