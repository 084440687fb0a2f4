"""Module system.

Reference: python/hetu/layers/ (30 files; base.py:15 OpLayer).  The reference's
layers build graph subtrees; ours are functional modules for jit/pjit:

    module = Linear(128, 64)
    variables = module.init(key)              # {"params": ..., "state": ...}
    y, new_state = module.apply(variables, x, train=True, rng=key2)

Uniform contract (every module):
  * ``init(key) -> {"params": pytree, "state": pytree}``  — "state" holds
    non-trainable buffers (BatchNorm running stats); {} when stateless.
  * ``apply(variables, x, *, train=False, rng=None) -> (y, new_state)``
    — always returns the (possibly unchanged) state so composition is
    mechanical and the whole model stays one pure function.

Child RNG streams derive deterministically via fold_in(child_index), the
module-level analog of the framework's (seed, seqnum) discipline (rng.py).
"""

from __future__ import annotations

from typing import Sequence

import jax


def child_rng(rng, i: int):
    return None if rng is None else jax.random.fold_in(rng, i)


def held_as(tree, dtype):
    """Every leaf of ``tree`` in ``dtype``: the leaf itself where it already
    is (no copy, no new buffer), else cast, once.  A shape standing for its
    array (``jax.eval_shape``'s trees, from which an engine is built only to
    be compiled) is re-typed."""
    def one(leaf):
        if leaf.dtype == dtype:
            return leaf
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf.update(dtype=dtype)
        return leaf.astype(dtype)

    return jax.tree_util.tree_map(one, tree)


class Module:
    """Base module; subclasses override init/apply."""

    def init(self, key) -> dict:
        return {"params": {}, "state": {}}

    def apply(self, variables, *args, train: bool = False, rng=None):
        raise NotImplementedError

    def serving_params(self, params):
        """``params`` as this module's forward READS them, for a server
        that holds the weights and never updates them: a leaf the forward
        reads only as a whole-leaf ``astype(compute dtype)`` comes back in
        that dtype (the ``astype`` at the use site is then a no-op), every
        other leaf as given.  Stacked leaves (a leading layer axis) are
        taken as they are.  The default is the tree as given: right for a
        module that reads its leaves as stored (norms, embeddings), and
        never wrong."""
        return params

    # convenience: module(variables, x) == module.apply(...)
    def __call__(self, variables, *args, **kwargs):
        return self.apply(variables, *args, **kwargs)


class Sequential(Module):
    """Chain of modules (reference: layers/sequence.py Sequence)."""

    def __init__(self, *modules: Module):
        if len(modules) == 1 and isinstance(modules[0], (list, tuple)):
            modules = tuple(modules[0])
        self.modules: Sequence[Module] = modules

    def init(self, key):
        params, state = {}, {}
        for i, m in enumerate(self.modules):
            v = m.init(jax.random.fold_in(key, i))
            params[str(i)] = v["params"]
            state[str(i)] = v["state"]
        return {"params": params, "state": state}

    def apply(self, variables, x, *, train: bool = False, rng=None):
        new_state = {}
        for i, m in enumerate(self.modules):
            v = {"params": variables["params"][str(i)],
                 "state": variables["state"][str(i)]}
            x, s = m.apply(v, x, train=train, rng=child_rng(rng, i))
            new_state[str(i)] = s
        return x, new_state


class Lambda(Module):
    """Wrap a stateless function as a module."""

    def __init__(self, fn):
        self.fn = fn

    def apply(self, variables, x, *, train: bool = False, rng=None):
        return self.fn(x), {}
