"""Persisted tuned block tables for the Pallas kernels.

``tune_flash.py`` sweeps block sizes on a chip and calls
:func:`save`; ``ops.attention`` / ``ops.decode`` call :func:`load` at
import so every later process (bench worker, user notebook) picks the
tuned sizes up automatically.  A checkout has no table until someone
tunes and commits one; without it the kernels use their 128-wide
defaults (``chip_smoke.py`` says which it ran with).

JSON schema (tuple keys are comma-joined ints — JSON has no tuples)::

    {"flash":  {"Sq,Sk,D,group": [block_q, block_k], ...},
     "decode": {"T,D,group": block_k, ...},
     "measured_at": "...", "device": "..."}
"""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tuned_blocks.json")


def _parse_key(s: str) -> tuple:
    return tuple(int(x) for x in s.split(","))


def load(path: str | None = None):
    """Returns (flash_table, decode_table); both empty when the file
    is absent or unreadable (the kernels then use their defaults)."""
    try:
        with open(path or PATH) as f:
            raw = json.load(f)
        flash = {_parse_key(k): tuple(int(b) for b in v)
                 for k, v in raw.get("flash", {}).items()}
        decode = {_parse_key(k): int(v)
                  for k, v in raw.get("decode", {}).items()}
        return flash, decode
    except (OSError, ValueError, TypeError, AttributeError):
        # AttributeError covers wrong-schema files (top level or a
        # sub-table not a dict): a malformed table must degrade to
        # kernel defaults, never break import of ops.attention/decode.
        return {}, {}


def save(flash: dict, decode: dict, meta: dict | None = None,
         path: str | None = None) -> str:
    """Atomically write the tables; returns the path written."""
    path = path or PATH
    raw = {"flash": {",".join(map(str, k)): list(map(int, v))
                     for k, v in flash.items()},
           "decode": {",".join(map(str, k)): int(v)
                      for k, v in decode.items()}}
    raw.update(meta or {})
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(raw, f, indent=1)
    os.replace(tmp, path)
    return path
