"""The configuration's graph, cached per checkout, and the run's copy of
it relabelled by the seed.

The graph of a configuration is fixed by its file, as a published dataset
is: its generator runs once per checkout and writes ``graph.npz`` (the
layout of the program's ``save_graph``, uncompressed) under
``<bench>/.cache/<config>/``. Every run then loads it and relabels the
vertices by a permutation drawn from ``--seed`` on the device, so each
seed serves its own isomorphic copy: the same sizes, another layout.
Reference vectors of the base graph are cached beside it and permuted the
same way.
"""
from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _atomic_save(path: Path, save: Callable[[str], None]) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + ".tmp")
    save(str(tmp))
    os.replace(tmp, path)


def base_graph(cfg: dict, cache_dir: Path, generate: Callable
               ) -> Tuple[int, np.ndarray, np.ndarray, bool]:
    """``(n, row_ptr, col_idx, generated)`` of the configuration's graph;
    ``generated`` is True where this call made it."""
    path = cache_dir / cfg["name"] / "graph.npz"
    generated = not path.is_file()
    if generated:
        n, row_ptr, col_idx = generate(cfg["graph"])

        def save(p):
            with open(p, "wb") as f:
                np.savez(f, n=np.int64(n), row_ptr=row_ptr, col_idx=col_idx,
                         epoch=np.int64(0), mutation_offset=np.int64(0))
        _atomic_save(path, save)
    with np.load(path) as z:
        return (int(z["n"]), np.asarray(z["row_ptr"], np.int32),
                np.asarray(z["col_idx"], np.int32), generated)


def cached_vector(cfg: dict, cache_dir: Path, name: str,
                  compute: Callable[[], np.ndarray]) -> np.ndarray:
    """A reference vector of the base graph, computed once per checkout."""
    path = cache_dir / cfg["name"] / f"{name}.npy"
    if not path.is_file():
        vec = compute()

        def save(p):
            with open(p, "wb") as f:
                np.save(f, vec)
        _atomic_save(path, save)
    return np.load(path)


@functools.partial(jax.jit, static_argnames=("n", "nnz"))
def _relabel(row_ptr, col_idx, key, *, n: int, nnz: int):
    perm = jax.random.permutation(key, n).astype(jnp.int32)  # new id of v
    inv = jnp.zeros(n, jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32))                      # old id of v'
    deg = row_ptr[1:] - row_ptr[:-1]
    new_deg = deg[inv]
    new_row_ptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(new_deg, dtype=jnp.int32)])
    new_src = jnp.repeat(jnp.arange(n, dtype=jnp.int32), new_deg,
                         total_repeat_length=nnz)
    old_arc = (row_ptr[inv[new_src]]
               + jnp.arange(nnz, dtype=jnp.int32) - new_row_ptr[new_src])
    return new_row_ptr, perm[col_idx[old_arc]], new_deg, perm


def relabel(n: int, row_ptr: np.ndarray, col_idx: np.ndarray, seed: int):
    """The graph with vertex ``v`` renamed ``perm[v]``, on the device:
    ``(row_ptr, col_idx, out_deg, perm)`` as int32 device arrays."""
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % 2**32), 0xB7A9)
    out = _relabel(jnp.asarray(row_ptr), jnp.asarray(col_idx), key,
                   n=n, nnz=int(col_idx.shape[0]))
    return jax.block_until_ready(out)
