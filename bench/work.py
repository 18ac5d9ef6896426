"""Operations and bytes the selection sweep needs, from its shapes, and
the least time the chip could take for them.

One selection step of one run scores, at each lookahead level ``l`` in
``0..la``, ``M · k^l`` states (the root alone at level 0), and for each
state every one of the M candidates.  Per (state, candidate) the sweep
needs, whatever implements it:

* the ensemble descent: per tree and level one compare of the point's
  feature against the node's threshold and one step to the child (2 ops),
  then one leaf read per tree;
* the posterior: B adds and a divide for the mean; B subtracts, B
  multiplies, B adds, a divide and a square root for the spread; the
  floor and the censored adjustment (4 ops);
* the acquisition: ``z`` (2), the normal cdf (15) and pdf (8), EI (5), the
  constraint probability (cdf 15, its argument 3), the budget filter's
  ``z`` and compare (3), the masked, rounded score and running argmax (4).

Bytes are what must cross HBM once per state: the forest (per tree
``depth · 2^(depth-1)`` split features and thresholds, 4 bytes each, and
``2^depth`` leaf values), the state's costs (4 bytes a point) and its
observed and censored masks (1 byte each), plus the space's ``F · M``
features and ``M`` unit prices once per call.  Outputs (a few words per
state) are counted as 16 bytes a state.

The sweep has no matrix product: every operation above is elementwise
float32 work for the vector unit, so its compute bound is the vector
unit's rate (``vpu_f32_ops_per_s`` in peaks.json), not the matrix unit's.
"""

from __future__ import annotations

ACQ_OPS = 2 + 15 + 8 + 5 + 15 + 3 + 3 + 4
POST_OPS = 4


def per_point_ops(n_trees: int, depth: int) -> int:
    descent = n_trees * (2 * depth + 1)
    moments = (n_trees + 1) + (3 * n_trees + 2)
    return descent + moments + POST_OPS + ACQ_OPS


def per_state_bytes(m: int, n_trees: int, depth: int) -> int:
    width = 2 ** (depth - 1)
    forest = n_trees * depth * width * 8 + n_trees * 2 ** depth * 4
    return forest + m * (4 + 1 + 1) + 16


def select_step(m: int, f: int, n_trees: int, depth: int, la: int,
                k_gh: int) -> dict:
    """``{"ops", "bytes", "states", "calls"}`` of one selection step of one
    run (one busy seat for one step)."""
    states = sum(m * k_gh ** level if level else 1 for level in range(la + 1))
    calls = la + 1
    return {
        "states": states,
        "calls": calls,
        "ops": states * m * per_point_ops(n_trees, depth),
        "bytes": (states * per_state_bytes(m, n_trees, depth)
                  + calls * (f + 1) * m * 4),
    }


def roofline_s(work: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for ``work``, and which bound
    sets it (``"compute"`` or ``"memory"``)."""
    tc = work["ops"] / peaks["vpu_f32_ops_per_s"]
    tm = work["bytes"] / peaks["bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
