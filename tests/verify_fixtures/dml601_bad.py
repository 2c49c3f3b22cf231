"""Fixture: donation DECLARED but silently DROPPED — exactly 1 DML601.

The jitted step donates its state argument, so the AST donation rule
(DML205) is satisfied and stays quiet — the declaration is right there
in the ``jax.jit`` call. But the step returns a per-column reduction of
the state, 256 bytes where the donated buffer holds 16,384: no output is
large enough to take the donated pages, so the donation is dropped at
compile time with nothing but a warning, and the caller has lost its
state for nothing. Only the compiled artifact's alias table (DML601) can
see this. (A dtype mismatch at equal byte size does NOT drop a donation:
XLA:CPU under jax 0.9 aliases an int32 buffer to a float32 output.)
"""

import jax
import jax.numpy as jnp


def dropped_donation_step(state, batch):
    # the output is a DIFFERENT SIZE: nothing can reuse the donated pages
    return (state * 2.0 + batch).sum(axis=0)


step_jit = jax.jit(dropped_donation_step, donate_argnums=(0,))


def dml_verify_programs():
    from dmlcloud_tpu.lint.ir import ProgramSpec

    state = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    batch = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    return [
        ProgramSpec(
            name="dropped_donation_step",
            fn=step_jit,
            args=(state, batch),
            donate_argnums=(0,),
        )
    ]
