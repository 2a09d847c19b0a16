"""Rounding to a lower floating-point format inside a plain reference: the
lower-precision control (``FLOAT8_E4M3`` for a program that computes in
bfloat16) and the witness (``BFLOAT16``). A reference's ``loss_fn`` takes
``rounding=`` and puts ``fake_quant`` on every tensor the program keeps in
its compute type; ``calibrate.py`` passes the formats."""
import jax
import jax.numpy as jnp

FLOAT8_E4M3 = (4, 3)     # (exponent bits, mantissa bits): the control
BFLOAT16 = (8, 7)        # the witness


def fake_quant(x, rounding):
    """x rounded to a float of ``rounding`` = (exponent bits, mantissa bits)
    and back, gradient straight through. ``jax.lax.reduce_precision`` is the
    operation XLA keeps for this; a cast there and back is one it may drop
    (on the chip it drops it in a small program and keeps it in a large one).
    A format with fewer exponent bits than float32 gets a per-tensor scale to
    its largest finite value and is clipped to it, so nothing overflows."""
    if rounding is None:
        return x
    ebits, mbits = rounding

    @jax.custom_vjp
    def q(v):
        if ebits >= 8:
            return jax.lax.reduce_precision(v, ebits, mbits)
        top = (2.0 - 2.0 ** -mbits) * 2.0 ** (2 ** (ebits - 1) - 1)
        s = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / top
        return jax.lax.reduce_precision(jnp.clip(v / s, -top, top), ebits, mbits) * s

    q.defvjp(lambda v: (q(v), None), lambda _, g: (g,))
    return q(x)
