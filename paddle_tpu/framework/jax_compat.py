"""The two spots where the engine's call shape differs from jax's own.

One installation is supported (jax 0.9.0): ``jax.shard_map`` with
``axis_names``/``check_vma`` and the varying-manual-axes type system
are simply there. What is left is a keyword adapter and a ``pcast``
that tolerates a value which already varies.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma=True):
    """``jax.shard_map`` with ``axis_names`` optional (None = manual
    over every mesh axis)."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def pcast(val, axes, to="varying"):
    """``jax.lax.pcast`` over a pytree. Casting to "varying" skips the
    axes a leaf already varies over: jax 0.9.0 refuses a
    varying-to-varying cast, and a scan carry is marked once up front
    whatever its operands' types turn out to be."""
    if isinstance(axes, str):
        axes = (axes,)

    def leaf(a):
        want = tuple(axes)
        if to == "varying":
            have = getattr(jax.typeof(a), "vma", frozenset())
            want = tuple(ax for ax in want if ax not in have)
        return jax.lax.pcast(a, want, to=to) if want else a

    return jax.tree_util.tree_map(leaf, val)
