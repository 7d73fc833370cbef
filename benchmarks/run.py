"""``python3 benchmarks/run.py --workload <config>.<traffic> --seed <n>
--seconds <s> --trace <0|1>``: one run of one cell of ``BENCHMARK.json``.

One process, on the machine it is started on. It exits with another code
than 0 and prints no result unless JAX finds the chips the cell asks for
(for a rehearsal the caller sets ``JAX_PLATFORMS=cpu`` itself). The last
line of its standard output is the result object.
"""

import os
import sys
import time

if __name__ == "__main__":
    T_PROCESS_START = time.perf_counter()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks import harness

    sys.exit(harness.main(sys.argv[1:], T_PROCESS_START))
