#!/usr/bin/env python3
"""The chip's elementwise float32 rate: the vector-unit row of peaks.json.

    python3 bench/vpu_peak.py

One jitted program applies a chain of ``LINKS`` multiply-adds, with
coefficients passed in at run time so that nothing folds, to every element
of an array.  XLA fuses the chain into one pass that reads and writes each
element once, so the pass is bound by the vector unit and not by HBM.  A
multiply and an add count as two operations.  ``CALLS`` calls are timed
together, and the best of ``REPEATS`` such spans is printed as one JSON
line.  TPU only; the benchmark's runs
never run it.
"""

from __future__ import annotations

import json
import sys
import time

ELEMENTS = 8 * 128 * 16384
LINKS = 1024
CALLS = 20
REPEATS = 5


def chain(x, coef):
    for i in range(LINKS):
        x = x * coef[2 * i] + coef[2 * i + 1]
    return x


def main() -> int:
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("vpu_peak: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(chain)
    x = jnp.linspace(0.5, 1.5, ELEMENTS, dtype=jnp.float32)
    coef = jnp.asarray([0.999, 1e-4] * LINKS, jnp.float32)
    text = f.lower(x, coef).compile().as_text()
    f(x, coef).block_until_ready()
    best = float("inf")
    for _ in range(REPEATS):
        t = time.perf_counter()
        for _ in range(CALLS):
            y = f(x, coef)
        y.block_until_ready()
        best = min(best, (time.perf_counter() - t) / CALLS)
    ops = 2 * LINKS * ELEMENTS
    print(json.dumps({"kind": dev.device_kind, "ops": ops, "best_s": best,
                      "ops_per_s": ops / best,
                      "bytes_per_s": 8 * ELEMENTS / best,
                      "fusions": text.count("kind=kLoop")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
