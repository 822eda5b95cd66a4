"""The controls: the precision one step below a configuration's own.

A control is the plain reference put in the program's place and computed
in the nearest precision below the one the configuration states -- the
step that would tempt a later PR. Both configurations here state
bfloat16 compute, so their control is fp8 as fp8 training is done
(Micikevicius et al. 2022, "FP8 Formats for Deep Learning"): every
convolution and matrix product takes its operands rounded to
``float8_e4m3fn`` and, in the backward pass, its output's gradient
rounded to ``float8_e5m2``, each after scaling the tensor's largest
magnitude onto the format's; accumulation stays float32.

The references call ``quant.operand`` on every operand and
``quant.grad`` on every product; the reference itself passes ``None``.
The comparison that decides ``correct`` has to fail the control; the
benchmark's own runs never run it (``tools/calibrate.py`` and the tests
do).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _fake_quant(x, dtype, fmt_max):
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / fmt_max
    return ((x32 / scale).astype(dtype).astype(jnp.float32) * scale).astype(x.dtype)


def fp8_e4m3(x):
    """Round to e4m3 with a per-tensor scale; the gradient passes
    straight through."""
    return x + jax.lax.stop_gradient(_fake_quant(x, jnp.float8_e4m3fn, E4M3_MAX) - x)


@jax.custom_vjp
def fp8_e5m2_grad(y):
    """Identity whose gradient is rounded to e5m2 with a per-tensor scale."""
    return y


fp8_e5m2_grad.defvjp(
    lambda y: (y, None),
    lambda _, g: (_fake_quant(g, jnp.float8_e5m2, E5M2_MAX),),
)


class Quant(NamedTuple):
    operand: Callable  # applied to each operand of a convolution or matrix product
    grad: Callable     # applied to each such product (acts on its gradient)


FP8 = Quant(operand=fp8_e4m3, grad=fp8_e5m2_grad)
