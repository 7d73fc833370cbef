"""Classic-Paxos attempts per commit of the window: the program's
``engine_classic_rounds`` (rounds in which the classic attempt ran, summed on
the device and fetched with each decision) over the window's commits. 1.0 when
every step needs one attempt; more means coordinators failed and the rotation
went on; 0 means the fast round decided. A program that keeps no such counter
reads nothing."""


def read(run):
    before, after = run["counters_before"].get("consensus"), run["counters_after"].get("consensus")
    commits = len(run.get("commit_ms") or ())
    if after is None or not commits:
        return None
    return (after["classic_rounds"] - (before or {"classic_rounds": 0})["classic_rounds"]) / commits
