"""Kernel timing by chained scan: the one definition of the protocol.

bench.py's flash cell, ``tune_flash.py`` and ``profile_attrib.py``
share these constants, so their numbers are taken the same way.
Whether a plain host clock around ``block_until_ready`` plus the device
trace should replace this protocol is the benchmark issue's to decide
(ROADMAP D2); until then this is how a kernel is timed.

The protocol:

- Each measured call runs ``n`` iterations of ``step`` chained through
  the scan CARRY (a real data dependency no scheduler can elide), all
  inside ONE jitted program.
- Per-call time is the (long - short chain) difference divided by the
  iteration delta: the fixed dispatch+fetch round-trip cancels.
- Each chain length is the MEDIAN of ``reps`` timed calls, every call
  on a DIFFERENT input value and ending in a host VALUE fetch, so a
  timed call can neither be served from a cache nor return before the
  device finished.
"""

from __future__ import annotations

import time

import jax

# The carry accumulates step(c) * CARRY_FACTOR: 1/64 is > ulp at
# magnitude 1 in bf16, so every scan iteration sees genuinely
# different values.  FRESH_FACTOR scales each timed call's input so no
# two calls (including the compile warm-up) share input values.
CARRY_FACTOR = 0.015625
FRESH_FACTOR = 0.03125


def chain_program(step, n: int):
    """One jitted program: ``n`` iterations of ``c + step(c) *
    CARRY_FACTOR`` chained through the scan carry."""
    def body(c, _):
        return c + step(c) * CARRY_FACTOR, None

    return jax.jit(lambda q: jax.lax.scan(body, q, None, length=n)[0])


def median_fresh_s(g, x, reps: int = 5):
    """Median wall-time of ``reps`` fresh-input calls of ``g`` (plus
    the raw samples); compiles+warms on ``x`` first."""
    float(g(x).sum())                     # compile + one run
    ts = []
    for i in range(reps):
        xi = x * (1.0 + FRESH_FACTOR * (i + 1))
        t0 = time.time()
        float(g(xi).sum())                # host value fetch
        ts.append(time.time() - t0)
    return sorted(ts)[len(ts) // 2], ts


def chained_delta_ms(step, x, n1: int = 2, n2: int = 18,
                     reps: int = 5):
    """Per-call milliseconds of ``step`` via the chained-delta
    protocol.  Returns ``(ms, samples)`` where ``samples`` carries the
    raw per-rep wall times for both chain lengths; ``ms`` <= 0 means
    measurement noise won — callers must retry or report None, never
    publish the number."""
    hi, hs = median_fresh_s(chain_program(step, n2), x, reps)
    lo, ls = median_fresh_s(chain_program(step, n1), x, reps)
    ms = (hi - lo) / (n2 - n1) * 1e3
    return ms, {"lo_s": [round(t, 4) for t in ls],
                "hi_s": [round(t, 4) for t in hs]}
