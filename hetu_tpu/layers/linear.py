"""Linear and convolution layers.

Reference: python/hetu/layers/{linear.py,conv.py}.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from hetu_tpu import init as initializers
from hetu_tpu import ops
from hetu_tpu.layers.base import Module, held_as


class Linear(Module):
    def __init__(self, in_features: int, out_features: int, *,
                 bias: bool = True, weight_init=None, bias_init=None,
                 activation=None, dtype=jnp.float32):
        self.in_features = in_features
        self.out_features = out_features
        self.use_bias = bias
        self.weight_init = weight_init or initializers.xavier_uniform()
        self.bias_init = bias_init or initializers.zeros()
        self.activation = activation
        self.dtype = dtype

    def init(self, key):
        # params are made f32 (master weights); self.dtype is the COMPUTE
        # dtype applied at use time, so bf16 training keeps full-precision
        # optimizer updates.  A server may hand the layer its weights
        # already cast (serving_params): the cast at use is then a no-op
        kw, kb = jax.random.split(key)
        params = {"weight": self.weight_init(
            kw, (self.in_features, self.out_features), jnp.float32)}
        if self.use_bias:
            params["bias"] = self.bias_init(kb, (self.out_features,),
                                            jnp.float32)
        return {"params": params, "state": {}}

    def apply(self, variables, x, *, train: bool = False, rng=None):
        p = variables["params"]
        # compute in self.dtype (bf16 on TPU keeps f32 master weights and
        # f32 MXU accumulation via preferred_element_type in ops.linear)
        w = p["weight"].astype(self.dtype)
        b = p.get("bias")
        y = ops.linear(x.astype(self.dtype), w,
                       None if b is None else b.astype(self.dtype))
        if self.activation is not None:
            y = self.activation(y)
        return y, {}

    def serving_params(self, params):
        # mirrors apply: weight and bias are both read as astype(self.dtype)
        return held_as(params, self.dtype)


class Conv2d(Module):
    """NCHW conv layer (reference: layers/conv.py Conv2d)."""

    def __init__(self, in_channels: int, out_channels: int, kernel_size,
                 stride=1, padding=0, *, bias: bool = True, weight_init=None,
                 bias_init=None, activation=None, dtype=jnp.float32):
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = (kernel_size, kernel_size) if isinstance(
            kernel_size, int) else tuple(kernel_size)
        self.stride = stride
        self.padding = padding
        self.use_bias = bias
        self.weight_init = weight_init or initializers.he_normal()
        self.bias_init = bias_init or initializers.zeros()
        self.activation = activation
        self.dtype = dtype

    def init(self, key):
        # f32 master weights; self.dtype is the compute dtype (see Linear)
        kw, kb = jax.random.split(key)
        w_shape = (self.out_channels, self.in_channels) + self.kernel_size
        params = {"weight": self.weight_init(kw, w_shape, jnp.float32)}
        if self.use_bias:
            params["bias"] = self.bias_init(kb, (self.out_channels,),
                                            jnp.float32)
        return {"params": params, "state": {}}

    def apply(self, variables, x, *, train: bool = False, rng=None):
        p = variables["params"]
        w = p["weight"].astype(self.dtype)
        x = x.astype(self.dtype)
        if self.use_bias:
            # bias stays uncast: the conv accumulates in f32 for bf16 inputs
            # (ops/conv.py preferred_element_type), so the add promotes
            y = ops.conv2d_add_bias(x, w, p["bias"],
                                    stride=self.stride, padding=self.padding)
        else:
            y = ops.conv2d(x, w, stride=self.stride, padding=self.padding)
        if self.activation is not None:
            y = self.activation(y)
        return y, {}


class Embedding(Module):
    """Dense embedding table (reference: layers/embedding.py).

    ``impl='auto'`` routes the lookup (and its scatter-add gradient)
    through the Pallas scalar-prefetch kernels on TPU — the
    EmbeddingLookUp.cu analog — and plain XLA elsewhere; ``'xla'`` forces
    the XLA gather (required when this layer's table is SPMD-sharded,
    which the partitioner can't do through a pallas_call).
    """

    def __init__(self, num_embeddings: int, embedding_dim: int, *,
                 weight_init=None, dtype=jnp.float32, impl: str = "xla"):
        if impl not in ("auto", "xla", "pallas"):
            raise ValueError(f"impl {impl!r}: 'auto', 'xla' or 'pallas'")
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.weight_init = weight_init or initializers.normal(stddev=0.01)
        self.dtype = dtype
        self.impl = impl

    def init(self, key):
        return {"params": {"weight": self.weight_init(
            key, (self.num_embeddings, self.embedding_dim), self.dtype)},
            "state": {}}

    def apply(self, variables, indices, *, train: bool = False, rng=None):
        w = variables["params"]["weight"]
        if self.impl != "xla":
            from hetu_tpu.ops.pallas_kernels import routed_gather
            rows = routed_gather(w, indices.reshape(-1))
            return rows.reshape(*indices.shape, self.embedding_dim), {}
        return ops.embedding_lookup(w, indices), {}
