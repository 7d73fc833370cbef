"""Share of the whole-wave loop's lockstep rounds in which the view-change
gate opened (``engine_fleet_commit_rounds`` over ``engine_fleet_wave_rounds``,
the window's differences): 100 for a loop that applies the view change in
every round, about a third where a wave is three rounds and one cut."""
from benchmarks.targets_fleet_join import window_counts


def read(run):
    counts = window_counts(run, "engine_fleet_commit_rounds", "engine_fleet_wave_rounds")
    if not counts or not counts[1]:
        return None
    return 100.0 * counts[0] / counts[1]
