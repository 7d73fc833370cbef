"""The plain reference: membership as set arithmetic, in numpy.

A crash-stop member that the protocol has had time to detect is out of the
view; a joiner whose admission was injected is in; nobody else moves. The
model shares no code with the engine and takes nothing from it: it sees only
the schedule the generator drew from the seed, and is compared with the view
fetched from the system.
"""

from __future__ import annotations

import numpy as np

#: Every number compared is a count of violations, so every limit is 0.
LIMITS = {
    "healthy_evicted": 0,
    "crashed_in_view": 0,
    "strangers_in_view": 0,
    "unresolved": 0,
    "cut_sizes_unaccounted": 0,
    "config_id_not_advanced": 0,
    "view_changes_out_of_range": 0,
}


class MembershipModel:
    """Expected membership of ``tenants`` independent clusters of ``slots``."""

    def __init__(self, initial_alive: np.ndarray):
        self._initial = np.array(initial_alive, dtype=bool)
        self.reset()

    def reset(self) -> None:
        self.expected = self._initial.copy()
        self.crashed = np.zeros_like(self._initial)
        self.events = np.zeros(self._initial.shape[0], dtype=np.int64)

    def apply(self, crash: np.ndarray, join: np.ndarray) -> None:
        """``crash``/``join``: [m, 2] arrays of (tenant, slot)."""
        for pairs, becomes in ((crash, False), (join, True)):
            if not len(pairs):
                continue
            t, s = pairs[:, 0], pairs[:, 1]
            if (self.expected[t, s] == becomes).any() or (becomes and self.crashed[t, s].any()):
                raise ValueError("schedule crashes a non-member or joins a member")
            self.expected[t, s] = becomes
            if not becomes:
                self.crashed[t, s] = True
            np.add.at(self.events, t, 1)

    def sizes(self) -> np.ndarray:
        return self.expected.sum(axis=1)

    def compare_view(self, alive: np.ndarray) -> dict:
        """Violation counts of a fetched ``alive`` mask [tenants, slots]."""
        alive = np.asarray(alive, dtype=bool)
        return {
            "healthy_evicted": int((self.expected & ~alive).sum()),
            "crashed_in_view": int((self.crashed & alive).sum()),
            "strangers_in_view": int((alive & ~self.expected & ~self.crashed).sum()),
        }

    def compare_epochs(self, before: dict, after: dict) -> dict:
        """View changes per tenant between two fetched views, against the
        events injected in between: a tenant with events changes its view at
        least once and at most once per event, and its configuration id
        differs; a tenant without events keeps epoch and id."""
        cuts = np.asarray(after["epoch"], dtype=np.int64) - np.asarray(before["epoch"], dtype=np.int64)
        moved = (after["config_hi"] != before["config_hi"]) | (after["config_lo"] != before["config_lo"])
        low = (self.events > 0).astype(np.int64)
        return {
            "view_changes_out_of_range": int(((cuts < low) | (cuts > self.events)).sum()),
            "config_id_not_advanced": int((moved != (cuts > 0)).sum()),
        }


def failures(numbers: dict) -> int:
    """How many of the compared numbers are over their limit."""
    return sum(1 for name, value in numbers.items() if value > LIMITS[name])
