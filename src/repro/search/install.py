"""O(unit)-memory candidate install: dynamic-slice tree surgery.

A proposal touches ONE unit of the fake-quant stack, so the engine keeps ONE
stack plus K per-unit candidate buffers instead of K full stacks:

- :func:`tree_install_unit` writes one unit into the stacked tree via
  ``jax.lax.dynamic_update_slice`` (the generalized ``_tree_update``; for a
  concrete integer index the two lower identically, pinned by the property
  tests);
- :func:`stack_unit_batch` stacks the K candidate units into the K × unit
  candidate buffer (a REAL stage output in the engine's staged pipeline, so
  ``jax.live_arrays()`` sees exactly the memory model being claimed);
- :func:`eval_candidates_unit` folds a ``lax.map`` over the K unit buffers,
  installing each into the stack one at a time — peak live memory is
  stack + K × unit, and it returns ``(primary, aux)`` vectors of shape (K,);
- :func:`tree_bytes` sizes a pytree for the memory-model stats.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["tree_install_unit", "stack_unit_batch", "eval_candidates_unit",
           "tree_bytes"]


def tree_install_unit(tree, u, unit):
    """Install ``unit`` (per-unit leaves) at index ``u`` (traced ok) along
    the leading axis of every leaf of the stacked ``tree``.

    Explicit ``dynamic_update_slice`` rather than ``x.at[u].set(n)`` so the
    O(unit) write is the lowered program by construction, not an indexing
    idiom the compiler may or may not canonicalize the same way.
    """
    def one(x, n):
        starts = (u,) + (jnp.int32(0),) * (x.ndim - 1)
        return jax.lax.dynamic_update_slice(
            x, n[None].astype(x.dtype), starts)

    return jax.tree.map(one, tree, unit)


def stack_unit_batch(units):
    """[unit pytree] * K -> one pytree with a leading K axis (the candidate
    buffer: K × unit, NOT K × stack)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *units)


def eval_candidates_unit(unit_batch, fq_stack, u, eval_fn):
    """Evaluate K candidates with O(unit) extra memory.

    ``lax.map`` runs the body sequentially, so only ONE installed stack is
    live at a time; the loop-carried state is nothing but the (K,) loss
    rows. ``eval_fn(fq) -> (primary, aux)`` is the full objective forward.
    """
    def body(unit_fq):
        return eval_fn(tree_install_unit(fq_stack, u, unit_fq))

    return jax.lax.map(body, unit_batch)


def tree_bytes(tree) -> int:
    """Total payload bytes of a pytree of arrays (memory-model reporting)."""
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))
