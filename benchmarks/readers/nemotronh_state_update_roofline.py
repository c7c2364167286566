"""The decode step's state update's share of its roofline: the bytes of
state and convolution tails the slice's steps read and wrote (the
program's count, ``readers/nemotronh_slice.py``) over the HBM peak, over
the device time of the decode step's events whose HLO text carries the
state's shape (``args.match``)."""

from benchmarks.model import nemotronh_flops as F
from benchmarks.readers.nemotronh_slice import counted


def read(obs: dict, args: dict):
    got = counted(obs, args)
    if got is None:
        return None
    seconds, totals = got
    if not totals["state_bytes"]:
        return None
    counts = F.state_update_counts(obs["cfg"], totals["state_bytes"])
    return 100.0 * F.roofline_seconds(counts, obs["peak"])["seconds"] \
        / seconds
