"""Share of the whole-wave loop's lockstep rounds in which the ``classic`` arm
ran (``engine_fleet_classic_rounds`` over ``engine_fleet_wave_rounds``, the
window's differences): the arm is taken when SOME tenant's recovery delay has
run out, so this is how often one tenant's fallback makes the whole fleet pay
for the attempt. 0 where every tenant decides fast."""
from benchmarks.targets_fleet_join import window_counts


def read(run):
    counts = window_counts(run, "engine_fleet_classic_rounds", "engine_fleet_wave_rounds")
    if not counts or not counts[1]:
        return None
    return 100.0 * counts[0] / counts[1]
