"""Grouped matrix multiplication for the expert layer.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies each run of rows of
``lhs`` (m, k) by its group's matrix of ``rhs`` (g, k, n): rows
``[sum(sizes[:i]), sum(sizes[:i+1]))`` by ``rhs[i]``.  Rows past
``sum(group_sizes)`` belong to no group; their output is left unspecified
(the kernel never writes them), so callers mask them.

On a TPU it runs the Pallas megablox kernels (``gmm`` forward, ``gmm`` with
the transposed right operand and ``tgmm`` backward) under a custom VJP of
our own, so that each of the three calls gets tiles that divide its own
dimensions (megablox's own VJP reuses the forward's tiles, which do not
divide a transposed 1792 x 2048 problem).  The grid only visits the tiles
of the rows in groups.  Elsewhere it is ``jax.lax.ragged_dot``, whose
gradients JAX derives; ``interpret=True`` runs the kernels in interpret
mode, which the tests compare with that fallback.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

from repro import kernels as lane  # default_interpret, looked up at call time

# the kernels' module (the package rebinds the name ``gmm`` to its own VJP)
megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

#: preferred tile of the row, contraction and output dimensions
TILE_M, TILE_K, TILE_N = 512, 1024, 1024


def _fit(dim: int, pref: int, align: int) -> int:
    """The largest multiple of ``align`` that divides ``dim`` and is at most
    ``pref``; ``dim`` itself where it is no larger than ``pref`` or nothing
    divides it."""
    if dim <= pref:
        return dim
    for t in range(pref - pref % align, 0, -align):
        if dim % t == 0:
            return t
    return dim


def tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Tiles of an (m, k) x (k, n) grouped product on the TPU (rows in
    sublane multiples of 8, the others in lane multiples of 128)."""
    return _fit(m, TILE_M, 8), _fit(k, TILE_K, 128), _fit(n, TILE_N, 128)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_gmm(lhs, rhs, group_sizes, interpret: bool):
    m, k = lhs.shape
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiles(m, k, rhs.shape[2]),
                        interpret=interpret)


def _kernel_fwd(lhs, rhs, group_sizes, interpret):
    return _kernel_gmm(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _kernel_bwd(interpret, res, g):
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = rhs.shape[2]
    dlhs = megablox.gmm(g, rhs, group_sizes, lhs.dtype, tiles(m, n, k),
                        transpose_rhs=True, interpret=interpret)
    drhs = megablox.tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype, tiles(m, k, n),
                         num_actual_groups=rhs.shape[0], interpret=interpret)
    return dlhs, drhs, None


_kernel_gmm.defvjp(_kernel_fwd, _kernel_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   interpret: bool | None = None) -> jax.Array:
    """(m, k) rows sorted by group times (g, k, n) -> (m, n) in lhs's dtype.
    ``interpret=None``: the kernels on a TPU, ``ragged_dot`` elsewhere."""
    group_sizes = group_sizes.astype(jnp.int32)
    if interpret is None and lane.default_interpret():
        return jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype)
    return _kernel_gmm(lhs, rhs, group_sizes, bool(interpret))
