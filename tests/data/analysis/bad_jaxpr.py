"""Known-bad fixture for the jaxpr lint layer (imported via importlib by
``tests/test_analysis.py``; not a test module).

``baked_scale`` bakes a python scalar into the traced program — two
"class members" differing only in that scalar trace different jaxprs, the
exact failure mode of a static coordinate leaking out of a compile key.
``drifting_carry`` violates the scan-carry contract by widening its dtype
every step; ``stable_carry`` is the well-behaved control."""
import jax.numpy as jnp


def baked_scale(x, scale):
    # `scale` arrives as a python float -> becomes a jaxpr constant
    return x * scale


def drifting_carry(carry, x):
    # int32 carry comes back float32: every scan step would re-trace
    return carry.astype(jnp.float32) + x.sum(), x.max()


def stable_carry(carry, x):
    return carry + x.sum().astype(carry.dtype), x.max()


# ------------------------------------------------ row-wide work (per cycle)
def whole_bank_select(bank, rows, vals, pred):
    # a per-point choice between two versions of the whole bank
    return jnp.where(pred, bank.at[rows].set(vals), bank)


def in_place_update(bank, rows, vals):
    return bank.at[rows].set(vals)


def loop_carrying_the_bank(bank, n):
    # batched over points with their own ``n``, the loop selects every
    # carried row on every trip
    import jax

    return jax.lax.while_loop(
        lambda c: c[0] < n, lambda c: (c[0] + 1, c[1].at[c[0]].add(1)),
        (0, bank))[1]
