"""The lower precision of the control: operands of every matrix product and
convolution rounded to fp8 (e4m3, one scale a tensor, straight-through
gradient), the step below the bfloat16 that the configurations state.

``backward_only`` plants a fault in the backward pass of one product alone,
its forward exact: the same rounding, or the weight's gradient with its sign
turned. Either is a fault that a distance weighted by the leaves' norms cannot
see where it sits in leaves of small norm, and the second leaves every norm as
it was."""
from __future__ import annotations


def operand(x, quant):
    """x as the product sees it: unchanged, or rounded through fp8."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError("unknown precision %r" % (quant,))
    import jax
    import jax.numpy as jnp

    # reduce_precision and not a pair of casts, which XLA may drop under jit;
    # 4 exponent and 3 mantissa bits, the tensor's largest value scaled to 224
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 224.0
    q = jax.lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3) * scale
    return x + jax.lax.stop_gradient(q - x)


def backward_only(product, quant):
    """``product(x, w)`` with an exact forward pass, whose backward pass sees
    both operands and the incoming gradient through ``quant``, or, for
    ``"negated"``, is exact but for the sign of ``w``'s gradient."""
    import jax

    @jax.custom_vjp
    def f(x, w):
        return product(x, w)

    def fwd(x, w):
        return product(x, w), (x, w)

    def bwd(saved, dy):
        x, w = saved
        if quant == "negated":
            dx, dw = jax.vjp(product, x, w)[1](dy)
            return dx, -dw
        _, vjp = jax.vjp(product, operand(x, quant), operand(w, quant))
        return vjp(operand(dy, quant))

    f.defvjp(fwd, bwd)
    return f
