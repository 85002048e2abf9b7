"""One spelling of ``jax.shard_map`` for the package: keyword-only, with
``axis_names`` (the manual axes) and ``check_vma`` passed only when set.
"""

from __future__ import annotations

from jax import shard_map as _sm


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=None):
    kw = {}
    if check_vma is not None:
        kw["check_vma"] = check_vma
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return _sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kw)
