"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths execute
without TPU hardware (the driver separately dry-runs the multichip path).
Environment must be set before jax is first imported.
"""

# Force CPU whatever the machine has: the suite's sharded tests need the 8
# virtual devices, and a test session must not hold the chip. A run on the
# chip opts out explicitly (RAPID_TPU_TEST_PLATFORM=tpu) to run the
# TPU-gated tests (e.g. the Mosaic-vs-jnp equivalence check) on real
# hardware.
import os

from rapid_tpu.utils.platform import force_platform

_plat = os.environ.get("RAPID_TPU_TEST_PLATFORM", "cpu")
if _plat not in ("cpu", "tpu"):
    # A typo must not silently route the whole suite onto the chip.
    raise RuntimeError(
        f"RAPID_TPU_TEST_PLATFORM={_plat!r}: expected 'cpu' (default) or "
        "'tpu' (a run on the chip)"
    )
if _plat == "cpu":
    # Not an assert: python -O would strip it, silently leaving tests on the
    # accelerator.
    if not force_platform("cpu", n_host_devices=8):
        raise RuntimeError(
            "could not force the CPU platform: a jax backend was initialized "
            "before tests/conftest.py ran; tests must not take the chip"
        )


# Build the native host library once per test session (load-only at runtime).
from rapid_tpu.utils._native import ensure_built

ensure_built()


# Property-test budget dial: HYPOTHESIS_PROFILE=thorough multiplies every
# property/fuzz test's example budget 5x (nightly / pre-release depth).
# Hypothesis profiles can't do this (per-test @settings decorators take
# precedence over a loaded profile), so the dial scales each collected
# test's decorator settings instead — the attachment point hypothesis
# reads at call time. Default runs keep the committed per-test budgets.
# Gated: a container without hypothesis must still run the non-property
# suite (the property/fuzz modules fail collection individually under
# --continue-on-collection-errors; an unconditional import here would take
# the whole session down with them).
try:
    import hypothesis
except ImportError:  # pragma: no cover - environment-dependent
    hypothesis = None

if hypothesis is not None and os.environ.get("HYPOTHESIS_PROFILE") == "thorough":

    def pytest_collection_modifyitems(items):
        scaled = set()  # parametrized items share one function: scale ONCE
        for item in items:
            fn = getattr(item, "function", None)
            spec = getattr(fn, "_hypothesis_internal_use_settings", None)
            if spec is not None and id(fn) not in scaled:
                scaled.add(id(fn))
                fn._hypothesis_internal_use_settings = hypothesis.settings(
                    spec, max_examples=spec.max_examples * 5
                )
        # The attachment point is a hypothesis-private attribute: if an
        # upgrade renames it, every spec lookup above returns None and the
        # dial silently becomes a 1x no-op. Fail fast instead — unless the
        # selected subset genuinely contains no property tests.
        has_hypothesis_items = any(
            getattr(item, "function", None) is not None
            and getattr(item.function, "hypothesis", None) is not None
            for item in items
        )
        if has_hypothesis_items and not scaled:
            raise RuntimeError(
                "HYPOTHESIS_PROFILE=thorough scaled zero tests although "
                "hypothesis-driven items were collected: the "
                "_hypothesis_internal_use_settings attachment point has "
                "moved; update the dial in tests/conftest.py"
            )
