"""The plain reference of the cut detector in time: which receiver announces
which cut in which round, and what the consensus makes of the announcements.

``consensus_model.py`` derives what every detector ENDS with; where the
network delivers an alert to different receivers in different rounds, what a
receiver announces depends on WHEN it looks, and two receivers of one cluster
can announce different cuts by delivery order alone (Rapid, USENIX ATC 2018,
"K, H, L sensitivity study", Fig. 11). This module replays that, round by
round, in numpy. It shares no code with the engine and is handed, once at
set-up and as data: the observer table, the cohort assignment, the watermarks,
the victims, and the delay of every (cohort, victim, ring) edge.

Per cohort (one receiver state shared by its members), round by round:

- a victim's healthy observers all fire in the same round, ``fd_threshold``
  probes after the crash, and the report of edge (victim, ring) arrives
  ``delay`` rounds later; an observer that is itself a victim fires nothing;
- a subject with L or more reports and fewer than H is in flux; for it, an
  edge whose observer is itself at L or more counts as reported
  (``MultiNodeCutDetector``'s edge invalidation, one pass a round, as
  ``consensus_model.reports`` has it at the fixpoint);
- the cohort announces, once, in the first round in which some subject is at
  H and none is in flux; what it announces is the set at H.

Per cluster, from the announcements: every live member of a cohort votes its
cohort's cut in the round it is announced; identical cuts pool their votes;
a value that holds ``consensus_model.fast_quorum`` votes decides in that
round; if none does within ``fallback_rounds`` rounds of the first
announcement, the classic round's coordinator picks the value most of its
quorum voted (``Paxos.java:271-328``; everybody hears everybody here, so the
quorum is every live member), and ``None`` where two values tie: the rule
allows either.
"""

from __future__ import annotations

import numpy as np

from benchmarks import consensus_model

#: No cohort of a deployment with delays of a few rounds is silent this long.
MAX_ROUNDS = 192


def announcements(*, observers: np.ndarray, victims: np.ndarray, delays: np.ndarray,
                  high: int, low: int, fd_threshold: int):
    """``(round[c], cut[c, f])``: the round in which each cohort announces
    (-1: never within ``MAX_ROUNDS``) and the cut it announces, a mask over
    ``victims``.

    ``observers``: [k, slots], -1 where a ring has no observer; ``victims``:
    [f] slots; ``delays``: [c, f, k] rounds from an edge's firing to its
    arrival at the cohort. Rounds count from 0, the round after the crash."""
    victims = np.asarray(victims)
    watcher = observers[:, victims].T  # [f, k]: who reports edge (victim, ring)
    index_of = np.full(observers.shape[1], -1, dtype=np.int64)
    index_of[victims] = np.arange(len(victims))
    by_victim = np.where(watcher >= 0, index_of[np.maximum(watcher, 0)], -1)  # [f, k]
    fires = (watcher >= 0) & (by_victim < 0)
    arrive = np.where(fires[None], fd_threshold - 1 + delays, MAX_ROUNDS)  # [c, f, k]
    cohorts = delays.shape[0]
    announced = np.full(cohorts, -1, dtype=np.int64)
    cuts = np.zeros((cohorts, len(victims)), dtype=bool)
    bits = np.zeros(arrive.shape, dtype=bool)
    for round_ in range(MAX_ROUNDS):
        if (announced >= 0).all():
            break
        bits |= arrive <= round_
        heard = bits.any(axis=(1, 2))
        tally = bits.sum(axis=2)
        flux = (tally >= low) & (tally < high)
        # one pass a round: an edge of a subject in flux whose observer is a
        # victim the cohort holds at L or more counts as reported
        pending = (tally >= low)[:, np.maximum(by_victim, 0)] & (by_victim >= 0)[None]  # [c, f, k]
        bits |= pending & flux[:, :, None] & heard[:, None, None]
        tally = bits.sum(axis=2)
        stable = tally >= high
        now = (announced < 0) & stable.any(axis=1) & ~((tally >= low) & ~stable).any(axis=1)
        announced[now] = round_
        cuts[now] = stable[now]
    return announced, cuts


def decision(*, members: int, live: np.ndarray, announced: np.ndarray, cuts: np.ndarray,
             fallback_rounds: int) -> dict:
    """What the cluster decides first, from its cohorts' announcements.

    ``live``: [c] members of each cohort that can vote (its healthy members);
    ``announced`` / ``cuts``: as :func:`announcements` returns them. Returns
    ``path`` (``fast``, ``classic`` or ``none``), ``round`` (the decision's),
    ``cut`` (the decided mask over the victims; ``None`` where two values tie
    in the coordinator's quorum), ``whole`` (the decided cut is every victim:
    the cluster is done in one view change), ``dissent`` (cohorts that had
    announced another cut than the decided one by then; ``None`` with
    ``cut``), ``votes`` / ``quorum`` (the most identical fast votes at the
    decision against what a fast round needs)."""
    quorum = consensus_model.fast_quorum(members)
    result = {"path": "none", "round": None, "cut": None, "whole": False, "dissent": None,
              "votes": 0, "quorum": quorum}
    if not (announced >= 0).any():
        return result
    first = int(announced[announced >= 0].min())
    for round_ in range(first, first + fallback_rounds):
        said = (announced >= 0) & (announced <= round_)
        values = {}  # cut -> votes: identical cuts pool
        for cohort in np.flatnonzero(said):
            key = cuts[cohort].tobytes()
            values[key] = values.get(key, 0) + int(live[cohort])
        ranked = sorted(values.items(), key=lambda item: -item[1])
        result["votes"] = ranked[0][1]
        if ranked[0][1] >= quorum:
            path, key = "fast", ranked[0][0]
            break
    else:
        # The classic round, in the round the recovery delay runs out: every
        # live member promises and is heard, so the quorum holds every vote.
        if int(live.sum()) < consensus_model.majority(members):
            return result
        path = "classic"
        key = None if len(ranked) > 1 and ranked[0][1] == ranked[1][1] else ranked[0][0]
    if key is None:
        return dict(result, path=path, round=round_)
    cut = np.frombuffer(key, dtype=bool)
    return dict(result, path=path, round=round_, cut=cut, whole=bool(cut.all()),
                dissent=int((said & (cuts != cut).any(axis=1)).sum()))


def expectation(*, members: int, observers: np.ndarray, cohort_of: np.ndarray,
                victims: np.ndarray, delays: np.ndarray, high: int, low: int,
                fd_threshold: int, fallback_rounds: int) -> dict:
    """:func:`decision` of one cluster under one schedule, with the cohorts'
    announcements beside it (``announced``, ``cuts``). ``cohort_of``: [slots],
    over the first ``members`` of which the cluster's members sit."""
    announced, cuts = announcements(
        observers=observers, victims=victims, delays=delays, high=high, low=low,
        fd_threshold=fd_threshold)
    cohorts = delays.shape[0]
    healthy = np.ones(members, dtype=bool)
    healthy[victims] = False
    live = np.bincount(cohort_of[:members][healthy], minlength=cohorts)
    return dict(
        decision(members=members, live=live, announced=announced, cuts=cuts,
                 fallback_rounds=fallback_rounds),
        announced=announced, cuts=cuts)
