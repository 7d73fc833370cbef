"""Probes the link-fault lane failed per engine round of the window: the
program's ``engine_link_probes_lost`` (summed on the device, fetched with each
decision) over the rounds the driver reported. While a faulty set of m
members at loss p is in the view it reads about 2 K m p a round: K edges into
and K out of every faulty member; 0 in rounds after the cut. A program that
keeps no such counter reads nothing."""


def read(run):
    before, after = run["counters_before"].get("link"), run["counters_after"].get("link")
    if after is None or not run.get("rounds"):
        return None
    lost = after["probes_lost"] - (before or {"probes_lost": 0})["probes_lost"]
    return lost / run["rounds"]
