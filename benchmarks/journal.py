"""The window's rows of the program's dispatch journal.

``rapid_tpu/utils/engine_telemetry.py`` keeps one row for every closed
``_dispatch`` block of the process (``DISPATCH_RECORD``: phase, driver, ``seq``,
the membership change it served, its start, the moment its wait for the device
began, its end, and what the process did meanwhile) and one for every closed
membership change (``CHANGE_RECORD``), in two rings that overwrite. A reader has
no handle on the driver and the harness keeps no clock reading of the window's
ends, so the window is found through what the harness does keep: a row carries
its phase's ``engine_dispatch`` sum after it, the very float the driver's
histogram held then, and ``run["counters_before"/"counters_after"]`` are those
sums at the window's ends. A row of phase p is the window's iff ``before[p] <
cum_ms <= after[p]`` and its driver is the window's, which is the driver of the
row whose ``cum_ms`` IS ``after[p]``. A change is the window's iff its last
dispatch is. So for every phase the rows' durations add up to the difference of
the sums that ``phase_ms.py`` and ``host_blocked_share`` read.

A program that keeps no journal (the parent of the PR that brought it) reads
nothing, and the result line leaves the metrics out.
"""

from __future__ import annotations

import numpy as np


def window(run):
    """``{"phases", "dispatches", "changes", "all"}`` (the window's rows,
    oldest first, and every row the ring holds), or ``None`` where the program
    keeps no journal or the ring has dropped rows of the window. Found once a
    run and kept on it: twelve readers ask."""
    if "journal_window" not in run:
        run["journal_window"] = _find(run)
    return run["journal_window"]


def _find(run):
    from rapid_tpu.utils import engine_telemetry

    if not hasattr(engine_telemetry, "journal_snapshot"):
        return None
    kept = engine_telemetry.journal_snapshot()
    return select(kept, run["counters_before"]["dispatch_ms"], run["counters_after"]["dispatch_ms"])


def select(kept: dict, before: dict, after: dict):
    rows, names = kept["dispatches"], kept["phases"]
    if not len(rows):
        return None
    low = np.asarray([before.get(name, 0.0) for name in names])[rows["phase"]]
    high = np.asarray([after.get(name, -np.inf) for name in names])[rows["phase"]]
    closing = rows["driver"][rows["cum_ms"] == high]
    inside = (low < rows["cum_ms"]) & (rows["cum_ms"] <= high)
    if len(closing):
        inside &= rows["driver"] == closing[-1]
    if inside[0] and kept["dispatches_written"] > len(rows):
        return None  # the ring's oldest row is the window's: older ones are gone
    dispatches, changes = rows[inside], kept["changes"]
    mine = np.isin(changes["seq_last"], dispatches["seq"])
    if len(dispatches):
        mine &= changes["driver"] == dispatches["driver"][0]
    return {"phases": names, "dispatches": dispatches, "changes": changes[mine], "all": rows}


def durations_ms(rows) -> np.ndarray:
    return (rows["t_end"] - rows["t_start"]) * 1e3


def waiting(rows):
    """The rows that waited for the device (their block was stamped)."""
    return rows[~np.isnan(rows["t_wait"])]


def steps(run) -> int:
    """The window's steps as ``phase_ms.py`` counts them."""
    return len(run.get("commit_ms") or ()) or run["attempted"]


def change_ms(found) -> np.ndarray:
    """Milliseconds every change of the window was pending."""
    return (found["changes"]["t_close"] - found["changes"]["t_open"]) * 1e3


def unphased_ms(found) -> np.ndarray:
    """Per change of the window: the time it was pending less the union of
    its dispatches' intervals, host time no phase covers."""
    rows = found["all"][np.argsort(found["all"]["change"], kind="stable")]
    ids = found["changes"]["change"]
    first = np.searchsorted(rows["change"], ids, side="left")
    last = np.searchsorted(rows["change"], ids, side="right")
    out = change_ms(found)
    for i, (a, b) in enumerate(zip(first, last)):
        covered, reach = 0.0, -np.inf
        for start, end in sorted(zip(rows["t_start"][a:b], rows["t_end"][a:b])):
            covered += max(0.0, end - max(start, reach))
            reach = max(reach, end)
        out[i] -= covered * 1e3
    return out


def split_ms(found):
    """Per change of the window ``(enqueue, wait)``: the milliseconds its
    dispatches spent before their wait began (a dispatch that never waited is
    all enqueue) and the milliseconds they waited. With the unphased part the
    two add up to ``change_ms`` wherever a change's dispatches do not nest."""
    rows, ids = found["all"], found["changes"]["change"]
    if not len(ids):
        return np.zeros(0), np.zeros(0)
    order = np.argsort(ids, kind="stable")
    at = np.minimum(np.searchsorted(ids[order], rows["change"]), len(ids) - 1)
    mine = ids[order][at] == rows["change"]
    wait = np.where(np.isnan(rows["t_wait"]), 0.0, rows["t_end"] - rows["t_wait"])
    enqueue = (rows["t_end"] - rows["t_start"]) - wait
    out = []
    for part in (enqueue, wait):
        summed = np.zeros(len(ids))
        summed[order] = np.bincount(at[mine], weights=part[mine], minlength=len(ids))
        out.append(summed * 1e3)
    return tuple(out)


def quarters(values) -> list:
    """The medians of the four quarters of ``values``, which are in the order
    the window's changes closed."""
    return [float(np.median(part)) for part in np.array_split(values, 4)]


def slow(found) -> list:
    """The window's dispatches whose milliseconds a round (one round where
    the phase reports none) exceed twice their phase's median in the window,
    and exceed it by more than a millisecond: ``(row, ms a round, median)``."""
    rows = found["dispatches"]
    a_round = durations_ms(rows) / np.maximum(rows["rounds"], 1)
    out = []
    for phase in np.unique(rows["phase"]):
        of_phase = rows["phase"] == phase
        median = float(np.median(a_round[of_phase]))
        for i in np.flatnonzero(of_phase & (a_round > 2 * median) & (a_round - median > 1.0)):
            out.append((rows[i], float(a_round[i]), median))
    return sorted(out, key=lambda item: item[0]["t_start"])


def describe(found, row, a_round: float, median: float) -> str:
    """One slow dispatch with what the process was doing while it was open."""
    wait_ms = (row["t_end"] - row["t_wait"]) * 1e3
    return (
        f"slow dispatch: phase={found['phases'][row['phase']]} seq={row['seq']} "
        f"change={row['change']} ms={(row['t_end'] - row['t_start']) * 1e3:.3f} "
        f"rounds={row['rounds']} ms_a_round={a_round:.3f} phase_median={median:.3f} "
        f"wait_ms={'none' if np.isnan(wait_ms) else format(wait_ms, '.3f')} "
        f"compiles={row['compiles']} gc_ms={row['gc_s'] * 1e3:.3f} bytes={row['bytes']}"
    )
