"""Lint corpus (clean): dataflow provenance with every proof holding.

The silent twin of ``dataflow_observer_leak.py``: telemetry is written
from the engine but never read back (a one-way plane), and every fleet
op stays inside its own tenant row (elementwise + per-tenant reduction).
The ``dataflow`` family must stay silent on both.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp

N = 256
TENANTS = 4


class EngineState(NamedTuple):
    alive: jnp.ndarray  # [n] activity mask
    cuts: jnp.ndarray  # [n] per-slot counters


class TelemetryLanes(NamedTuple):
    tl_enq: jnp.ndarray  # [n] observer tally, write-only


def _observer_silent():
    # Telemetry observes the engine; nothing flows the other way.
    def step(state, telem):
        cuts = state.cuts + 1
        telem = TelemetryLanes(tl_enq=telem.tl_enq + cuts)
        return EngineState(alive=state.alive, cuts=cuts), telem

    return {
        "jit": jax.jit(step),
        "args": (
            EngineState(
                alive=jnp.ones((N,), jnp.bool_),
                cuts=jnp.zeros((N,), jnp.int32),
            ),
            TelemetryLanes(tl_enq=jnp.zeros((N,), jnp.int32)),
        ),
    }


def _per_tenant_fleet():
    # Elementwise work plus a per-tenant mean: every op keeps the tenant
    # axis intact, so no influence edge can cross it.
    def fleet(lanes):
        centered = lanes - lanes.mean(axis=1, keepdims=True)
        return centered * 2.0 + 1.0

    return {
        "jit": jax.jit(fleet),
        "args": (jnp.ones((TENANTS, 8), jnp.float32),),
    }


DATAFLOW_AUDIT_PROGRAMS = {
    "observer_silent": {
        "build": _observer_silent,
        "checks": ("observer-effect",),
    },
    "per_tenant_fleet": {
        "build": _per_tenant_fleet,
        "checks": ("cross-tenant",),
        "tenants": TENANTS,
    },
}
