"""Parameter initializers, realized as ops in the startup program
(reference: fluid/initializer.py — Constant/Uniform/Normal/Xavier/MSRA emit
fill_constant / uniform_random / gaussian_random startup ops)."""
from __future__ import annotations

import math

import numpy as np

from .core.program import Block, Variable, default_startup_program


class Initializer:
    def __call__(self, var: Variable, block: Block):
        raise NotImplementedError


class ConstantInitializer(Initializer):
    def __init__(self, value: float = 0.0):
        self.value = value

    def __call__(self, var, block):
        block.append_op("fill_constant", outputs={"Out": [var.name]},
                        attrs={"shape": list(var.shape),
                               "dtype": var.dtype.name,
                               "value": float(self.value)})


class UniformInitializer(Initializer):
    def __init__(self, low=-1.0, high=1.0, seed=0):
        self.low, self.high, self.seed = low, high, seed

    def __call__(self, var, block):
        block.append_op("uniform_random", outputs={"Out": [var.name]},
                        attrs={"shape": list(var.shape),
                               "dtype": var.dtype.name,
                               "min": float(self.low),
                               "max": float(self.high),
                               "seed": self.seed})


class NormalInitializer(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        block.append_op("gaussian_random", outputs={"Out": [var.name]},
                        attrs={"shape": list(var.shape),
                               "dtype": var.dtype.name,
                               "mean": float(self.loc),
                               "std": float(self.scale),
                               "seed": self.seed})


class TruncatedNormalInitializer(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        block.append_op("truncated_gaussian_random",
                        outputs={"Out": [var.name]},
                        attrs={"shape": list(var.shape),
                               "dtype": var.dtype.name,
                               "mean": float(self.loc),
                               "std": float(self.scale),
                               "seed": self.seed})


def _fan_in_out(var):
    shape = var.shape
    if len(shape) < 2:
        return (shape[0] if shape else 1,) * 2
    receptive = 1
    for s in shape[2:]:
        receptive *= s
    # conv weights are [out, in, kh, kw]; fc weights are [in, out].  A rank-3
    # parameter reads as a filter [out, in, k] here; a layer whose rank-3
    # parameter is a STACK of matrices [n, in, out] (layers.moe's experts,
    # bilinear_tensor_product) states the fans of one matrix through
    # XavierInitializer(fan_in=, fan_out=).
    if len(shape) == 2:
        return shape[0], shape[1]
    return shape[1] * receptive, shape[0] * receptive


class XavierInitializer(Initializer):
    """Glorot (fluid initializer.py XavierInitializer)."""

    def __init__(self, uniform=True, fan_in=None, fan_out=None, seed=0):
        self.uniform, self.fan_in, self.fan_out, self.seed = \
            uniform, fan_in, fan_out, seed

    def __call__(self, var, block):
        fi, fo = _fan_in_out(var)
        fi = self.fan_in or fi
        fo = self.fan_out or fo
        if self.uniform:
            limit = math.sqrt(6.0 / (fi + fo))
            UniformInitializer(-limit, limit, self.seed)(var, block)
        else:
            std = math.sqrt(2.0 / (fi + fo))
            NormalInitializer(0.0, std, self.seed)(var, block)


class MSRAInitializer(Initializer):
    """He initialization (fluid initializer.py MSRAInitializer)."""

    def __init__(self, uniform=True, fan_in=None, seed=0):
        self.uniform, self.fan_in, self.seed = uniform, fan_in, seed

    def __call__(self, var, block):
        fi, _ = _fan_in_out(var)
        fi = self.fan_in or fi
        if self.uniform:
            limit = math.sqrt(6.0 / fi)
            UniformInitializer(-limit, limit, self.seed)(var, block)
        else:
            std = math.sqrt(2.0 / fi)
            NormalInitializer(0.0, std, self.seed)(var, block)


class NumpyArrayInitializer(Initializer):
    """Initialize from a concrete array (assign-from-host)."""

    def __init__(self, value: np.ndarray):
        self.value = np.asarray(value)

    def __call__(self, var, block):
        block.append_op("assign_value", outputs={"Out": [var.name]},
                        attrs={"values": self.value,
                               "dtype": var.dtype.name,
                               "shape": list(self.value.shape)})


# fluid-style aliases
Constant = ConstantInitializer
Uniform = UniformInitializer
Normal = NormalInitializer
TruncatedNormal = TruncatedNormalInitializer
Xavier = XavierInitializer
MSRA = MSRAInitializer

_global_weight_initializer = XavierInitializer()
_global_bias_initializer = ConstantInitializer(0.0)
