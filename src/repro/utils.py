"""Small shared utilities (mesh construction, tree sizing, rng)."""

from __future__ import annotations

import os
from typing import Sequence, Tuple

import numpy as np

import jax


def shard_map_compat(
    f,
    mesh: jax.sharding.Mesh,
    in_specs,
    out_specs,
    axis_names=None,
    check: bool = False,
):
    """``jax.shard_map`` manual over ``axis_names`` (every mesh axis when
    ``None``), with ``check`` as its ``check_vma``."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)


def make_mesh(shape: Sequence[int], axis_names: Sequence[str]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis of Auto type."""
    return jax.make_mesh(
        tuple(shape),
        tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


def use_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache for an entry point; returns
    its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX
    (which reads it itself); otherwise the cache sits at ``<root>/.jax_cache``
    — one fixed path, since the path is part of what a cache hit matches.
    Entry points call this; importing the library never does, so the test
    suite compiles uncached."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def tree_size_bytes(tree) -> int:
    """Total bytes of all array leaves in a pytree (by shape/dtype, not
    device residency)."""
    return sum(
        int(np.prod(x.shape, dtype=np.int64)) * x.dtype.itemsize
        for x in jax.tree.leaves(tree)
        if hasattr(x, "shape") and hasattr(x, "dtype")
    )


def tree_param_count(tree) -> int:
    return sum(
        int(np.prod(x.shape, dtype=np.int64))
        for x in jax.tree.leaves(tree)
        if hasattr(x, "shape")
    )


def fold_seed(seed: int, *names: str) -> jax.Array:
    """Deterministic named rng derivation."""
    key = jax.random.key(seed)
    for n in names:
        key = jax.random.fold_in(key, int(np.uint32(abs(hash(n)) & 0xFFFFFFFF)))
    return key
