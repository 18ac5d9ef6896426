"""Parity of the compiled Pallas selector kernel against XLA, on a TPU.

    python3 scripts/chip_kernel_parity.py [--states 256] [--seed 0]

Needs a TPU: it exits 2 without one, since interpret mode on the CPU
compiles neither Mosaic nor the TPU's XLA and so cannot show what this
checks.  Two checks, printed as one JSON line:

* ``kernel_full`` / ``kernel_pick`` — the fused ``select_step`` kernel in
  both output modes against the pure-jnp reference (``select_step/ref.py``,
  compiled by XLA), over ``--states`` speculative states fit on the
  384-point TensorFlow space at the paper's forest size (10 trees of depth
  4): per output, the number of states whose values differ in any bit.
  Any difference in a decision (``sel``, ``cand``, ``has_cand``) exits 1.
* ``mosaic_argmax_rows_differ`` — a bare ``jnp.argmax`` over rows with
  exact ties, lowered once by Mosaic inside a kernel and once by XLA: the
  number of rows whose picks differ.  Quantized scores tie on purpose
  (``quantize_scores``), so a nonzero count is why the kernel reads the
  lowest index among the top scores instead of calling argmax.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

DECISIONS = ("sel", "cand", "has_cand")
FULL_NAMES = ("mu", "sigma", "eic", "ystar", "cand", "sel", "has_cand",
              "nodes")
PICK_NAMES = ("sel", "has_cand", "eic_sel", "mu_sel", "sig_sel", "nodes")


def selector_inputs(n_states: int, seed: int):
    """Per-state forests fit on random observations of the first TF job,
    plus the space and acquisition operands of ``select_step_call``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import Settings, lookahead
    from repro.core.acquisition import gauss_hermite
    from repro.jobs import tensorflow_jobs

    job = tensorflow_jobs(0)[0]
    points, left, thr, u = lookahead.space_arrays(job.space, job.unit_price)
    m = job.space.n_points
    s = Settings(n_trees=10, depth=4, k_gh=3)
    rng = np.random.default_rng(seed)
    y = jnp.asarray(rng.uniform(0.1, 2.0, (n_states, m)), jnp.float32)
    obs = jnp.asarray(rng.random((n_states, m)) < 0.1)
    cens = obs & jnp.asarray(rng.random((n_states, m)) < 0.3)
    beta = jnp.asarray(rng.uniform(0.5, 3.0, n_states), jnp.float32)
    bf = jnp.asarray(rng.uniform(0.2, 1.0, n_states), jnp.float32)
    params = jax.jit(lambda k, yy, oo: lookahead._fit_batch_params(
        k, yy, oo, points, left, thr, s))(jax.random.PRNGKey(seed), y, obs)
    xi = jnp.asarray(gauss_hermite(3)[0])
    return ((params.feat, params.thr, params.leaf, y, obs, beta, bf, points,
             u, jnp.float32(job.t_max), jnp.float32(1e-3), xi, cens),
            job.space.n_points)


def kernel_vs_ref(args, emit_full: bool, interpret: bool = False) -> dict:
    """States (rows) whose kernel output differs from the reference's in
    any bit, per output."""
    import jax
    import numpy as np
    from repro.kernels.select_step.kernel import select_step_call
    from repro.kernels.select_step.ref import select_step_ref

    *pos, xi, cens = args
    kw = dict(emit_full=emit_full, want_nodes=True)
    got = jax.jit(lambda *a: select_step_call(
        *a, xi, cens, interpret=interpret, **kw))(*pos)
    want = jax.jit(lambda *a: select_step_ref(*a, xi, cens, **kw))(*pos)
    names = FULL_NAMES if emit_full else PICK_NAMES
    out = {}
    for name, g, w in zip(names, got, want, strict=True):
        # Bytes per state: [S, 1] kernel columns compare with [S] refs.
        g, w = (np.ascontiguousarray(a).view(np.uint8).reshape(len(a), -1)
                for a in (g, w))
        rows = (g != w).any(axis=1)
        out[name] = int(rows.sum())
    return out


def mosaic_argmax_rows_differ(n_rows: int, m: int, seed: int,
                              interpret: bool = False) -> int:
    """Rows of exactly tied scores where a Mosaic ``argmax`` and XLA's
    ``argmax`` (lowest index among the maxima) pick differently."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    rng = np.random.default_rng(seed)
    # Few distinct values per row: every row's maximum is tied many times.
    scores = jnp.asarray(rng.integers(0, 4, (n_rows, m)), jnp.float32)

    def body(s_ref, o_ref):
        o_ref[...] = jnp.argmax(s_ref[...], axis=1).astype(
            jnp.int32)[:, None]

    mosaic = pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((n_rows, 1), jnp.int32),
        interpret=interpret)(scores)[:, 0]
    xla = jax.jit(lambda s: jnp.argmax(s, axis=1))(scores)
    return int((np.asarray(mosaic) != np.asarray(xla)).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--states", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_kernel_parity: needs a TPU, but JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    inputs, m = selector_inputs(args.states, args.seed)
    report = {"device_kind": dev.device_kind, "states": args.states,
              "kernel_full": kernel_vs_ref(inputs, emit_full=True),
              "kernel_pick": kernel_vs_ref(inputs, emit_full=False),
              "mosaic_argmax_rows_differ": mosaic_argmax_rows_differ(
                  args.states, m, args.seed)}
    print(json.dumps(report))
    bad = [f"{mode}.{k}" for mode in ("kernel_full", "kernel_pick")
           for k in DECISIONS if report[mode].get(k)]
    if bad:
        print(f"chip_kernel_parity: kernel decisions differ from XLA: {bad}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
