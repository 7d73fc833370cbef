"""The plain reference of one-way link faults: the paper's windowed failure
detector replayed edge by edge over a table of probe outcomes, in numpy.

A faulty member loses a share of what is sent TO it and keeps sending. One
round is one failure-detector interval. Given who observes whom, the faulty
set with its loss and its on/off schedule, and what every probe of every
round came to, the model says which edges have fired by which round, how many
reports each subject carries, and who may vote. It shares no code with the
engine and takes only tables from it. What the view should be in the end stays
``membership_model.MembershipModel``'s business: the faulty set plays its
crashed set.

The detector is the rule of ``rapid_tpu/monitoring/windowed.py`` (the paper's
section 7): an edge fires once its window of the last ``fd_window`` outcomes is
full and ``fd_threshold`` or more of them failed. With ``fd_window`` 0 it is
the shipped code's counter instead: an edge fires at its ``fd_threshold``-th
failure, whenever the others were.
"""

from __future__ import annotations

import numpy as np

#: Loss, in permille, of an ingress that is wholly dead.
DEAD = 1000


def schedule_on(round_since_set: int, on_rounds: int, off_rounds: int) -> bool:
    """Whether the faults are on in the given round after they were set:
    ``on_rounds`` on, ``off_rounds`` off, again and again; 0 off: always on."""
    if not off_rounds:
        return True
    return round_since_set % (on_rounds + off_rounds) < on_rounds


def loss_in_round(faulty, permille: int, members: int, round_since_set: int,
                  on_rounds: int = 0, off_rounds: int = 0) -> np.ndarray:
    """[members] ingress loss of every member in one round."""
    loss = np.zeros(members, dtype=np.int64)
    if schedule_on(round_since_set, on_rounds, off_rounds):
        loss[np.asarray(faulty, dtype=np.int64)] = permille
    return loss


def may_vote(faulty, permille: int, members: int, round_since_set: int,
             on_rounds: int = 0, off_rounds: int = 0) -> np.ndarray:
    """[members] bool: a member whose ingress is wholly dead in a round hears
    no proposal and casts no vote in it; everybody else may."""
    return loss_in_round(faulty, permille, members, round_since_set, on_rounds, off_rounds) < DEAD


def failure_probability(loss_subject, loss_observer) -> np.ndarray:
    """Chance that a probe fails: the request is lost at the subject's
    ingress or the reply at the observer's, independently."""
    p_s = np.asarray(loss_subject, dtype=np.float64) / DEAD
    p_o = np.asarray(loss_observer, dtype=np.float64) / DEAD
    return 1.0 - (1.0 - p_s) * (1.0 - p_o)


def false_reports(observers: np.ndarray, faulty) -> np.ndarray:
    """[members]: on how many of its rings a member is watched by a faulty
    one. ``observers`` is [k, members], -1 where a ring has no observer."""
    named = np.zeros(observers.shape[1], dtype=bool)
    named[np.asarray(faulty, dtype=np.int64)] = True
    return (named[np.clip(observers, 0, None)] & (observers >= 0)).sum(axis=0)


class EdgeDetectors:
    """One detector per (subject, ring) edge, stepped a round at a time."""

    def __init__(self, members: int, k: int, fd_window: int, fd_threshold: int,
                 rounds_seen: int = 0):
        """``rounds_seen``: quiet rounds every edge has already been probed
        for (a warmed detector: its window holds that many successes)."""
        self.window, self.threshold = int(fd_window), int(fd_threshold)
        self.history = [[[False] * min(rounds_seen, self.window) for _ in range(k)]
                        for _ in range(members)]
        self.failures = np.zeros((members, k), dtype=np.int64)
        self.fired = np.zeros((members, k), dtype=bool)
        self.fire_round = np.full((members, k), -1, dtype=np.int64)

    def step(self, round_number: int, probed: np.ndarray, failed: np.ndarray) -> np.ndarray:
        """One round: ``probed[s, j]`` says the edge's observer probed at all
        (a round without a probe leaves the edge as it was), ``failed[s, j]``
        what the probe came to. Returns the edges that fire in this round."""
        fires = np.zeros_like(self.fired)
        for s, j in zip(*np.nonzero(probed)):
            bad = bool(failed[s, j])
            if self.window:
                outcomes = self.history[s][j]
                outcomes.append(bad)
                del outcomes[: -self.window]
                due = len(outcomes) == self.window and sum(outcomes) >= self.threshold
            else:
                self.failures[s, j] += bad
                due = self.failures[s, j] >= self.threshold
            if due and not self.fired[s, j]:
                fires[s, j] = True
        self.fired |= fires
        self.fire_round[fires] = round_number
        return fires

    def reports(self) -> np.ndarray:
        """[members]: rings on which each subject has been reported."""
        return self.fired.sum(axis=1)


def replay(outcomes: np.ndarray, probed: np.ndarray, fd_window: int, fd_threshold: int,
           rounds_seen: int = 0, first_round: int = 0) -> EdgeDetectors:
    """Every edge's detector over ``outcomes[r, s, j]`` (True: the probe of
    round ``first_round + r`` failed) where ``probed[r, s, j]``."""
    rounds, members, k = outcomes.shape
    detectors = EdgeDetectors(members, k, fd_window, fd_threshold, rounds_seen)
    for r in range(rounds):
        detectors.step(first_round + r, probed[r], outcomes[r])
    return detectors
