"""Lint corpus: device->host syncs inside the traced convergence seams.

Every spelling of the round-trip the fused-dispatch design exists to avoid,
inside a ``*_impl`` function and the while-loop body it hands to lax: each
one is a blocking host round trip per round on the device.
"""

import numpy as np

import jax
import jax.numpy as jnp


def convergence_impl(state, max_steps):
    def cond(carry):
        return carry[1] < max_steps

    def body(carry):
        x, i = carry
        val = float(jnp.sum(x))  # expect: host-sync-in-hot-path
        host = np.asarray(x)  # expect: host-sync-in-hot-path
        x.block_until_ready()  # expect: host-sync-in-hot-path
        n = jnp.sum(x).item()  # expect: host-sync-in-hot-path
        fetched = jax.device_get(x)  # expect: host-sync-in-hot-path
        return x + val + host.mean() + n + fetched[0], i + 1

    out, _ = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
    return out
