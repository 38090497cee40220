"""The one routing point for version-sensitive jax symbols.

The installed jax spells them ``jax.shard_map`` (``check_vma`` /
``axis_names``) and ``pltpu.CompilerParams``; the earlier spellings
(``jax.experimental.shard_map`` with ``check_rep`` / ``auto``,
``pltpu.TPUCompilerParams``) are gone from it.  Everything in the
package reaches these symbols through this module, so the next rename
lands in exactly one place (pdtpu-lint's compat rule keeps it that way,
docs/ANALYSIS.md).
"""

from __future__ import annotations

import jax

__all__ = ["shard_map", "pallas_compiler_params"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None,
              axis_names=None, **kw):
    """``jax.shard_map``; ``None`` for ``check_vma`` / ``axis_names``
    means "jax's default" and is not forwarded."""
    if check_vma is not None:
        kw["check_vma"] = check_vma
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def pallas_compiler_params():
    """The ``pltpu.CompilerParams`` class."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams
