"""The plain reference of the consensus outcome: which path decides a cut, and
which cut, as counting in numpy.

It shares no code with the engine and takes nothing from it but the observer
table, fetched once at set-up (as ``targets_link.py::observers`` hands it
over). Everything else is the schedule: who crashed, which cohorts cannot hear
which senders, K/H/L, the recovery delay. From them it derives

- what every cohort's cut detector ends with (``reports``, ``proposal``): a
  receiver counts one report per ring whose observer is healthy and heard, and
  one implicit report per ring whose observer is itself reported at L or more
  (``MultiNodeCutDetector``'s edge invalidation); a subject between L and H
  holds the cohort's proposal back, a subject under L is not in it;
- whether a fast quorum ``N - floor((N - 1) / 4)`` of identical votes is
  reachable (``FastPaxos.java:125-156``): every live member of a cohort that
  announced votes its cohort's cut;
- if not, the value the classic round's coordinator picks
  (``Paxos.java:271-328``: among the phase-1 quorum's votes at the highest
  ``vrnd`` the value most of them hold, which is the one a fast quorum could
  have chosen if any could; no vote at all: any announced cut), for every
  kind of coordinator the partition distinguishes, and whether a majority can
  accept it.

The fast round is round 1 of the protocol, so every vote in a step carries
the same ``vrnd`` and "highest" picks among all of them.
"""

from __future__ import annotations

import numpy as np


def fast_quorum(members: int) -> int:
    return members - (members - 1) // 4


def majority(members: int) -> int:
    return members // 2 + 1


def reports(observers: np.ndarray, crashed: np.ndarray, unheard: np.ndarray, low: int) -> np.ndarray:
    """[slots]: the reports about each crashed member that a receiver ends
    with when it cannot hear the ``unheard`` senders (both masks over slots;
    ``observers`` is [k, slots], -1 where a ring has no observer)."""
    seat = observers >= 0
    watcher = np.where(seat, observers, 0)
    direct = (seat & ~crashed[watcher] & ~unheard[watcher]).sum(axis=0)
    tally = np.where(crashed, direct, 0)
    while True:
        # an edge whose observer is itself reported at L or more counts, for a
        # subject that is between the watermarks already
        implicit = (seat & crashed[watcher] & (tally[watcher] >= low)).sum(axis=0)
        grown = np.where(crashed & (direct >= low), direct + implicit, tally)
        if (grown == tally).all():
            return tally
        tally = grown


def proposal(tally: np.ndarray, high: int, low: int):
    """The cut a detector with these tallies announces (a mask over slots), or
    ``None`` while a subject between the watermarks holds it back (or nothing
    reached H)."""
    stable = tally >= high
    if ((tally >= low) & ~stable).any() or not stable.any():
        return None
    return stable


def outcome(*, members: int, alive: np.ndarray, cohort_of: np.ndarray, crashed: np.ndarray,
            deaf: np.ndarray, unheard: np.ndarray, observers: np.ndarray, high: int, low: int,
            fallback_rounds: int, announce_round=None) -> dict:
    """The decision of one configuration under one schedule.

    ``alive`` / ``crashed`` / ``unheard``: masks over slots; ``cohort_of``:
    [slots]; ``deaf``: mask over cohorts (a deaf cohort hears none of the
    ``unheard`` senders; the others hear everybody). ``announce_round`` is
    the round in which the hearing cohorts announce, where the schedule fixes
    it (every detector fires in the same round); the decision's round follows
    from it.

    Returns ``path`` (``fast``, ``classic`` or ``none``), ``cut`` (the decided
    mask, ``None`` where it depends on who coordinates), ``cuts`` (1 when the
    decided cut is the whole crashed set, else ``None``: more will follow),
    ``votes`` / ``quorum`` (the most identical fast votes against what a fast
    round needs), ``attempts`` (classic attempts: 1 when every kind of
    coordinator completes both phases, else ``None``) and ``round``.
    """
    none = np.zeros_like(crashed)
    cuts = {False: proposal(reports(observers, crashed, none, low), high, low),
            True: proposal(reports(observers, crashed, unheard, low), high, low)}
    live, in_deaf = alive & ~crashed, deaf[cohort_of]
    voters = {False: live & ~in_deaf, True: live & in_deaf}
    # identical proposals pool their votes
    same = cuts[False] is not None and cuts[True] is not None and (cuts[False] == cuts[True]).all()
    values = []  # (cut, mask of the members that voted it)
    if same:
        values.append((cuts[False], live))
    else:
        values += [(cuts[side], voters[side]) for side in (False, True) if cuts[side] is not None]
    result = {"path": "none", "cut": None, "cuts": None, "attempts": None, "round": None,
              "votes": max((int(voted.sum()) for _, voted in values), default=0),
              "quorum": fast_quorum(members)}
    if not values:
        return result

    def decided(path, cut, round_):
        whole = cut is not None and (cut == crashed).all()
        return dict(result, path=path, cut=cut, cuts=1 if whole else None, round=round_)

    best = max(values, key=lambda value: value[1].sum())
    if best[1].sum() >= result["quorum"]:
        return decided("fast", best[0], announce_round)
    # The classic round: what each kind of coordinator can do. A coordinator
    # is heard by everybody unless it is an unheard sender (then the deaf
    # cohorts miss it), and hears everybody unless its own cohort is deaf.
    picks, completes = [], True
    for coordinator_deaf in (False, True):
        for coordinator_unheard in (False, True):
            if not (voters[coordinator_deaf] & (unheard == coordinator_unheard)).any():
                continue
            hears_it = live & ~(in_deaf & coordinator_unheard)
            it_hears = live & ~(unheard & coordinator_deaf)
            promised = hears_it & it_hears
            if promised.sum() < majority(members):  # and whoever accepts has promised
                completes = False
                continue
            held = [int((voted & promised).sum()) for _, voted in values]
            if max(held) == 0:
                picks.append(None)  # a free choice among the announced cuts
            elif sorted(held)[-2:].count(max(held)) == 2:
                picks.append(None)  # a tie: the rule allows either
            else:
                picks.append(int(np.argmax(held)))
    if not picks:
        return result
    agreed = picks[0] is not None and all(pick == picks[0] for pick in picks)
    round_ = None if announce_round is None or not completes else announce_round + fallback_rounds - 1
    return dict(decided("classic", values[picks[0]][0] if agreed else None, round_),
                attempts=1 if completes else None)
