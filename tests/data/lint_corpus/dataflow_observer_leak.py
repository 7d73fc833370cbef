"""Lint corpus: dataflow provenance defects, one per proof check.

Two miniature traced programs in the registry spec shape, each
violating one property the ``dataflow`` family proves over the real
engine: a telemetry lane read back into an engine lane (the observer
perturbs its subject), and a gather whose indices cross the fleet's
tenant axis (tenant ``t`` reads tenant ``t+1``'s lanes).
``clean_dataflow.py`` is the silent twin.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp

N = 256
TENANTS = 4


class EngineState(NamedTuple):
    alive: jnp.ndarray  # [n] activity mask — a gating lane
    cuts: jnp.ndarray  # [n] per-slot counters


class TelemetryLanes(NamedTuple):
    tl_enq: jnp.ndarray  # [n] observer tally — must stay write-only


def _observer_feedback():
    # The telemetry tally flows back into the engine's cut counters: the
    # observer plane influences a subject lane.
    def step(state, telem):
        cuts = state.cuts + telem.tl_enq
        telem = TelemetryLanes(tl_enq=telem.tl_enq + 1)
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


def _cross_tenant_gather():
    # Each tenant's output row is gathered from ANOTHER tenant's input
    # row — an influence edge across the tenant axis.
    def fleet(lanes):
        return lanes[jnp.arange(TENANTS)[::-1]]

    return {
        "jit": jax.jit(fleet),
        "args": (jnp.ones((TENANTS, 8), jnp.float32),),
    }


DATAFLOW_AUDIT_PROGRAMS = {
    "observer_feedback": {  # expect: dataflow-observer-effect
        "build": _observer_feedback,
        "checks": ("observer-effect",),
    },
    "cross_tenant_gather": {  # expect: dataflow-cross-tenant
        "build": _cross_tenant_gather,
        "checks": ("cross-tenant",),
        "tenants": TENANTS,
    },
}
