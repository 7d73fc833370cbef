"""The delivery kernel's least time on this chip over its traced time.
Bytes and operations come from ``benchmarks/bytes.py``, peaks from
``benchmarks/peaks.json``; a device that is not in the table is an error."""
import json
import os

from benchmarks import bytes as kernel_bytes
from benchmarks.metrics import delivery_kernel_us

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    calls, seconds = delivery_kernel_us.calls_and_seconds(run)
    if not calls:
        return None
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as handle:
        peaks = json.load(handle)
    if run["device_kind"] not in peaks:
        raise KeyError(f"no peaks for device kind {run['device_kind']!r} in benchmarks/peaks.json")
    config = run["config"]
    need = kernel_bytes.delivery_new_bits(
        config["slots"], config["cohorts"], config["k"], config["pallas_lanes"])
    least, bound = kernel_bytes.least_seconds(need, peaks[run["device_kind"]])
    print(f"delivery_roofline: {need['bytes']} bytes, {need['ops']} ops, least {least * 1e6:.1f} us "
          f"({bound}-bound) against {seconds / calls * 1e6:.1f} us traced")
    return 100.0 * least / (seconds / calls)

