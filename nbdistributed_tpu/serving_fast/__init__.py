"""Serving fast path (ISSUE 17): paged KV blocks + closed-loop load.

Two deliberately dependency-free modules shared by the gateway driver,
the worker-side decode server, and the load harness:

- :mod:`.paging` — the fixed-size KV block allocator.  Pure host-side
  bookkeeping (no jax import): the gateway instantiates one allocator
  per decode rank to gate admission on free *blocks* instead of
  sequence slots, and the device layer (:mod:`..models.paged_kv`)
  instantiates the same class to manage physical block ids inside the
  pooled cache.  One implementation, two owners, identical arithmetic
  — the admission verdict and the device table can never disagree
  about capacity.
- :mod:`.loadgen` — the closed-loop load generator core: deterministic
  arrival/length schedules, a pluggable transport (HTTP shim or an
  in-process ``TenantClient``), SLO scoring against the PR 12
  TTFT/TPOT histograms, and a machine-readable report with a pinned
  schema.  ``tools/nbd_loadgen.py`` is a thin CLI over this module so
  the unit tests drive the exact code the CLI runs.
"""

from .loadgen import (LoadConfig, run_load, score_slo, synth_schedule,
                      validate_report)
from .paging import BlockAllocator, BlocksExhausted, blocks_needed

__all__ = ["BlockAllocator", "BlocksExhausted", "blocks_needed",
           "LoadConfig", "run_load", "score_slo", "synth_schedule",
           "validate_report"]
