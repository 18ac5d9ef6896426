"""The one home of jax APIs whose spelling has drifted across releases.

Every ``*_with_path`` tree walk, ``shard_map`` and ``cost_analysis`` use in
``src/`` goes through this module (the ``compat-drift`` lint rule in
``repro.analysis.ast_lint`` enforces it), so the next drift is fixed here
once.

Supported floor: jax 0.9.0, the installed release.
"""

from __future__ import annotations

from typing import Any, Callable

import jax

__all__ = ["tree_leaves_with_path", "shard_map", "cost_analysis_dict"]


def tree_leaves_with_path(tree: Any,
                          is_leaf: Callable[[Any], bool] | None = None):
    """``jax.tree.leaves_with_path``: a list of ``(key_path, leaf)``."""
    return jax.tree.leaves_with_path(tree, is_leaf=is_leaf)


def shard_map(f, *, mesh, in_specs, out_specs, **kwargs):
    """``jax.shard_map``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (``{}`` when missing)."""
    return dict(compiled.cost_analysis() or {})
