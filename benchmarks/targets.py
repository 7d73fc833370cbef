"""The system under test, built from a configuration file.

This is the only module of the benchmark that imports the program. Each
deployment kind (``cluster``: one ``VirtualCluster``; ``fleet``: one
``TenantFleet``) is wrapped so that the generators drive both through the
same few verbs: inject a step's faults, resolve them through the program's
own driver call, fetch the view for the check, restore a pristine state.
The wrappers add no work of their own to the timed path: every verb is one
or two calls of the program's public driver methods.
"""

from __future__ import annotations

import numpy as np

#: Dispatch phases of ``utils/dispatch.py`` in which the host waits for the
#: device (the others only enqueue).
BLOCKING_PHASES = (
    "sync", "run_to_decision", "run_until_membership", "fleet_decision",
    "fleet_wave", "health_scan", "stream_fetch",
)


def fold_seed(seed: int, *salt: int) -> np.random.SeedSequence:
    """Any whole number (the driver's seeds pass 2**32) to a seed sequence."""
    return np.random.SeedSequence([int(seed) & 0xFFFFFFFF, int(seed) >> 32, *salt])


class _Target:
    """What both deployments share: counters, pristine copies, the view."""

    def __init__(self, driver, config: dict, tenants: int):
        import jax
        import jax.numpy as jnp

        self.driver = driver
        self.tenants, self.members, self.slots = tenants, config["members"], config["slots"]
        self._clone = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))

    # -- counters and spans the program keeps (read by the metric readers) --

    def counters(self) -> dict:
        """Snapshot of the driver's transfer counters and of the host time
        spent per dispatch phase (milliseconds, exact sums)."""
        metrics = self.driver.metrics
        phases = metrics.phase_timings.get("engine_dispatch", {})
        return {
            "d2h_bytes": int(metrics.counters.get("engine_d2h_bytes", 0)),
            "dispatch_ms": {name: float(hist.sum) for name, hist in phases.items()},
        }

    # -- state ---------------------------------------------------------------

    def initial_alive(self) -> np.ndarray:
        alive = np.zeros((self.tenants, self.slots), dtype=bool)
        alive[:, : self.members] = True
        return alive

    def snapshot(self):
        """A device-resident copy of the state the driver will donate away."""
        import jax

        copy = (self._clone(self.driver.state), self.driver.faults)
        jax.block_until_ready(copy)
        return copy

    def restore(self, pristine) -> None:
        state, faults = pristine
        self.driver.state = self._clone(state)
        self.driver.faults = faults

    def view(self) -> dict:
        """The membership as the system holds it now, fetched for the check.
        Reads the state directly: the program's own observers charge their
        bytes to the transfer counter the commit path is measured by."""
        state = self.driver.state
        return {
            "alive": np.asarray(state.alive),
            "epoch": np.asarray(state.config_epoch),
            "config_hi": np.asarray(state.config_hi),
            "config_lo": np.asarray(state.config_lo),
        }


class ClusterTarget(_Target):
    kind = "cluster"

    def __init__(self, config: dict, seed: int, platform: str):
        from rapid_tpu.models.virtual_cluster import VirtualCluster

        seq = fold_seed(seed, 1)
        identity_seed, stagger_seed = (int(s) for s in seq.generate_state(2, np.uint64))
        vc = VirtualCluster.create(
            config["members"], n_slots=config["slots"], k=config["k"],
            h=config["h"], l=config["l"], cohorts=config["cohorts"],
            fd_threshold=config["fd_threshold"], seed=identity_seed,
            # The Mosaic kernel exists on the chip only; a CPU rehearsal takes
            # the jnp core, which the program's tests hold bit-identical.
            use_pallas=bool(config["use_pallas"]) and platform == "tpu",
            delivery_spread=config["delivery_spread"],
            concurrent_coordinators=config["concurrent_coordinators"],
            pallas_lanes=config["pallas_lanes"],
        )
        if config["cohort_assignment"] != "roundrobin":
            raise ValueError(f"unknown cohort_assignment {config['cohort_assignment']!r}")
        vc.assign_cohorts_roundrobin()
        if config.get("fd_stagger_rounds"):
            vc.stagger_fd_counts(
                np.random.default_rng(stagger_seed), config["fd_stagger_rounds"]
            )
        vc.sync()
        super().__init__(vc, config, tenants=1)

    def inject(self, crash, join) -> None:
        """``crash``/``join``: [m, 2] (tenant, slot) pairs; tenant is 0."""
        if len(crash):
            self.driver.crash(crash[:, 1])
        if len(join):
            self.driver.inject_join_wave(join[:, 1])
        self.driver.sync()

    def resolve(self, mode: str, target_members: int) -> dict:
        if mode == "until_membership":
            rounds, cuts, resolved, sizes = self.driver.run_until_membership(
                target_members, max_steps=192, max_cuts=4, min_cuts=1
            )
            return {
                "rounds": rounds, "lockstep_rounds": rounds, "cuts": cuts,
                "resolved": bool(resolved), "final_sizes": [sizes[-1] if sizes else -1],
            }
        if mode == "to_decision":
            rounds, decided, _, members = self.driver.run_to_decision(max_steps=64)
            return {
                "rounds": rounds, "lockstep_rounds": rounds, "cuts": int(decided),
                "resolved": bool(decided), "final_sizes": [members],
            }
        raise ValueError(f"unknown resolve mode {mode!r}")

    def view(self) -> dict:
        return {key: value[None] for key, value in super().view().items()}


class FleetTarget(_Target):
    kind = "fleet"

    def __init__(self, config: dict, seed: int, platform: str):
        from rapid_tpu.tenancy.fleet import TenantFleet

        tenants = config["tenants"]
        seeds = [int(s) for s in fold_seed(seed, 2).generate_state(tenants, np.uint64)]
        fleet = TenantFleet.create(
            tenants, config["members"], n_slots=config["slots"], k=config["k"],
            cohorts=config["cohorts"], seeds=seeds,
            knobs=[(config["h"], config["l"], config["fd_threshold"])] * tenants,
            delivery_spread=config["delivery_spread"],
        )
        fleet.sync()
        super().__init__(fleet, config, tenants)

    def inject(self, crash, join) -> None:
        if len(join):
            raise ValueError("a stacked fleet takes crashes only (tenancy/fleet.py)")
        self.driver.stream_crash(crash)

    def resolve(self, mode: str, target_members: int) -> dict:
        if mode != "to_decision":
            raise ValueError(f"unknown fleet resolve mode {mode!r}")
        rounds, decided, _, members = self.driver.run_to_decision(max_steps=64)
        return {
            "rounds": int(rounds.sum()), "lockstep_rounds": int(rounds.max()),
            "cuts": int(decided.sum()), "resolved": bool(decided.all()),
            "final_sizes": members.tolist(),
        }


TARGETS = {"cluster": ClusterTarget, "fleet": FleetTarget}


def build(config: dict, seed: int, platform: str):
    try:
        kind = TARGETS[config["deployment"]]
    except KeyError:
        raise ValueError(f"unknown deployment {config.get('deployment')!r}") from None
    return kind(config, seed, platform)
