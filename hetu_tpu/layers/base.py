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

import re
from typing import Sequence

import jax

from hetu_tpu.ops.matmul import linear, linear_minor


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


# a leaf held with its two minor axes exchanged goes by its name and this
HELD_TRANSPOSED = "_t"


def held_transposed(leaves: dict, **split) -> dict:
    """``leaves`` with each named leaf ``[..., K, N]`` held as ``[...,
    *split, K]`` under its name and ``HELD_TRANSPOSED``: its contracting
    axis minor and its output axis split into the axes its product's result
    is read by (``qkv_weight=(heads, 3, width)``; None: left whole), so
    that the product takes a layer of it as it lies, with no relayout and
    no reshape between (a shape standing for its array too).
    :func:`linear_held` reads either form."""
    def one(leaf, axes):
        axes = leaf.shape[-1:] if axes is None else tuple(axes)
        shape = leaf.shape[:-2] + axes + leaf.shape[-2:-1]
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(shape, leaf.dtype)
        return jax.numpy.swapaxes(leaf, -1, -2).reshape(shape)

    held = {k: v for k, v in leaves.items() if k not in split}
    held.update((name + HELD_TRANSPOSED, one(leaves[name], axes))
                for name, axes in split.items())
    return held


def linear_held(x, leaves: dict, name: str, dtype, bias=None):
    """``x @ leaves[name] (+ bias)`` in ``dtype`` over whichever form
    ``leaves`` holds the weight in: as given, cast at use, the result
    ``[..., N]``; or :func:`held_transposed`, the result ``[..., *split]``.
    The same contraction over the same operands in the same order."""
    held = leaves.get(name + HELD_TRANSPOSED)
    if held is None:
        # the weight's cast before the bias's: the order the train steps'
        # lowered text has (tests/test_kernel_lowering.py holds its digest)
        w = leaves[name].astype(dtype)
        return linear(x, w, None if bias is None else bias.astype(dtype))
    if bias is not None:
        bias = bias.astype(dtype).reshape(held.shape[:-1])
    return linear_minor(x, held, bias)


def held_sources(path: str) -> tuple:
    """The paths (``jax.tree_util.keystr``) of the given leaf that a held
    leaf at ``path`` may render: its own, its own less a layer's index
    (:func:`held_by_layer`), its own less ``HELD_TRANSPOSED``
    (:func:`held_transposed`)."""
    return (path, re.sub(r"\[\d+\]$", "", path),
            path.replace(HELD_TRANSPOSED + "']", "']"))


def held_by_layer(leaves: dict, *names) -> dict:
    """``leaves`` with each named leaf, stacked over layers, held as a tuple
    of its layers' arrays (a shape standing for its array too): ``held[l]``
    is then Python's indexing, and a program that reads layer ``l`` takes
    that array whole.  A leaf that already is such a tuple stays as given."""
    def layers(leaf):
        if isinstance(leaf, tuple):      # given so: a model may yield it
            return leaf
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return tuple(jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
                         for _ in range(leaf.shape[0]))
        return tuple(leaf[l] for l in range(leaf.shape[0]))

    return dict(leaves, **{name: layers(leaves[name]) for name in names})


class Module:
    """Base module; subclasses override init/apply."""

    def init(self, key) -> dict:
        return {"params": {}, "state": {}}

    def apply(self, variables, *args, train: bool = False, rng=None):
        raise NotImplementedError

    def serving_params(self, params):
        """``params`` as this module's forward READS them, for a server
        that holds the weights and never updates them.  Two things a
        program's read of a leaf depends on are fixed here, once, and not
        in every decode round and every prefill chunk:

        * the DTYPE: a leaf the forward reads only as a whole-leaf
          ``astype(compute dtype)`` comes back in that dtype (the
          ``astype`` at the use site is then a no-op);
        * WHERE THE BYTES LIE, for an attention projection leaf stacked
          over layers (PR 45; what the compiler for a TPU v5e was seen to
          do, ``PERF.md`` section 6).  A layer taken at a TRACED index, a
          scan's own slice or a ``dynamic_slice``, is read in place, the
          slice fused into the product; but where the product's result is
          read head by head at a head width under the 128 lanes, the
          compiler contracts over the leaf's minor axis with the result's
          head axes for the product's own: it relays a leaf stored ``[K,
          N]`` in every call (5% of GPT-2-large's busy time) and, with a
          reshape between the slice and the product, writes the slice out
          and reads it again (3% more).  Such a leaf is held TRANSPOSED,
          its columns split by head (:func:`held_transposed`, read by
          :func:`linear_held`).  A layer cut out at a STATIC index (a
          Python loop over layers) is written into a buffer of its own in
          every call (6% of K-EXAONE's), so such a leaf is held as a tuple
          of its layers' arrays (:func:`held_by_layer`).

        Every other leaf comes back as given, stacked leaves included: the
        FFN and out-projection leaves of the same trees are read in place.
        The default is the tree as given: right for a module that reads
        its leaves as stored (norms, embeddings), and never wrong.  What
        decides is the static structure of the module that owns the leaf,
        never a name or an option; ``apply``, ``init`` and checkpoints read
        ``params`` and know nothing of this rendering."""
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
