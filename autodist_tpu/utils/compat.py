"""The one spelling of ``shard_map`` the package uses.

Call sites pass the manual axes as ``axis_names`` (partial-manual mode: the
rest stay GSPMD-auto) and never import ``shard_map`` themselves
(``tools/check_patterns.py`` rule 1), so a future move of the API is one
edit here.
"""
from __future__ import annotations

from typing import Callable, Optional, Set


def shard_map(
    f: Callable,
    *,
    mesh,
    in_specs,
    out_specs,
    axis_names: Optional[Set[str]] = None,
    check_vma: bool = False,
):
    """``jax.shard_map`` with this package's defaults: ``check_vma`` off,
    and ``axis_names=None`` meaning every mesh axis is manual."""
    import jax

    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)
