"""The start-up seam: ``chip_smoke.py`` (body tiny on CPU, the script refusing
a non-TPU platform, its checks failing loudly), the compile-cache helper, the
native library's source-keyed build, and the engine's refusal to fall back
when the Mosaic kernel is asked for where it cannot compile.
"""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from rapid_tpu.utils import _native, platform

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

# The tiny geometry: the 1M-shape stage gets the churn's slot count (400 + 10
# joiner slots) so the two share their small compiled programs. Four of the
# session's virtual CPU devices (the environment's XLA_FLAGS carries over)
# stand in for a four-chip host, so the mesh stage runs through the driver.
_BODY = (
    "import jax, chip_smoke\n"
    "totals = chip_smoke.run_smoke(n_churn=400, cohorts_churn=8, n_xl=410,"
    " cohorts_xl=8, use_pallas=False, twin=False, trace=False, repeats=2,"
    " mesh_devices=jax.devices()[:4])\n"
    "print('TOTAL_COMPILES', totals['compiles'])\n"
)


@pytest.fixture(scope="module", autouse=True)
def cpu_runs():
    """The module's two ``python ...`` runs, held to the CPU and started
    together at the first test so they overlap each other and the in-process
    tests (tier-1 has no seconds to spare); the tests that read them come
    last. Processes of their own because that is how the script runs — and
    because the ~100 executables the body compiles must stay out of this
    session, which ends within 1 % of vm.max_map_count."""
    procs = {
        name: subprocess.Popen(
            [sys.executable, *args], stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=str(REPO), env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        for name, args in (("body", ("-c", _BODY)), ("script", ("chip_smoke.py",)))
    }
    try:
        yield procs
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
            proc.communicate()


class _UnresolvedCluster:
    """Stands in for VirtualCluster: every driver call is accepted, and the
    churn never resolves."""

    @classmethod
    def create(cls, *_args, **_kwargs):
        return cls()

    def __getattr__(self, _name):
        return lambda *_args, **_kwargs: None

    def run_until_membership(self, *_args, **_kwargs):
        return 5, 0, False, ()


def test_smoke_fails_when_a_phase_fails(monkeypatch):
    from rapid_tpu.models import virtual_cluster

    monkeypatch.setattr(virtual_cluster, "VirtualCluster", _UnresolvedCluster)
    with pytest.raises(RuntimeError, match="churn resolved"):
        chip_smoke.run_smoke(
            n_churn=400, cohorts_churn=8, n_xl=400, cohorts_xl=8,
            use_pallas=False, twin=False, trace=False,
        )


def test_trace_check_rejects_a_directory_without_a_trace(tmp_path):
    with pytest.raises(RuntimeError, match="xplane.pb landed"):
        chip_smoke.device_trace_events(str(tmp_path))


def test_trace_check_rejects_a_trace_without_a_device_plane(tmp_path):
    # A CPU trace is a real .xplane.pb with host planes only: the check
    # must say so, not pass on "a file exists".
    import jax

    with jax.profiler.trace(str(tmp_path)):
        pass
    with pytest.raises(RuntimeError, match="device plane with events") as err:
        chip_smoke.device_trace_events(str(tmp_path))
    assert "/host:CPU" in str(err.value)


# ---------------------------------------------------------------------------
# The compile-cache helper
# ---------------------------------------------------------------------------


@pytest.fixture
def cache_config():
    """The live jax cache-dir config, restored after the test."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_placed_from_outside_is_left_alone(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    before = cache_config.jax_compilation_cache_dir
    assert platform.enable_compile_cache() == str(tmp_path / "outside")
    assert cache_config.jax_compilation_cache_dir == before
    assert not (tmp_path / "outside").exists()  # jax owns it, not the helper


def test_cache_defaults_to_the_repo_directory(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert platform.enable_compile_cache() == str(REPO / ".jax_cache")
    assert cache_config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    assert (REPO / ".jax_cache").is_dir()


def test_default_cache_path_is_fixed(monkeypatch):
    # Resolved from the checkout alone: no home, temp, pid or clock component.
    path = str(platform.DEFAULT_CACHE_DIR)
    assert path == str(REPO / ".jax_cache")
    monkeypatch.setenv("HOME", "/somewhere/else")
    monkeypatch.setenv("TMPDIR", "/somewhere/tmp")
    assert str(platform.DEFAULT_CACHE_DIR) == path
    assert str(os.getpid()) not in Path(path).name
    assert ".jax_cache/" in (REPO / ".gitignore").read_text()


def test_cache_setup_failure_propagates(monkeypatch, tmp_path, cache_config):
    # No swallowing: a cache that cannot be set up must not read as "every
    # run compiles cold" with no trace of why.
    blocker = tmp_path / "a_file"
    blocker.write_text("")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(platform, "DEFAULT_CACHE_DIR", blocker / ".jax_cache")
    with pytest.raises(OSError):
        platform.enable_compile_cache()


@pytest.mark.parametrize("inherited,expected", [
    ("", "--xla_force_host_platform_device_count=8"),
    ("--xla_foo=1 --xla_force_host_platform_device_count=16",
     "--xla_foo=1 --xla_force_host_platform_device_count=8"),
])
def test_force_platform_requests_the_virtual_devices(monkeypatch, inherited, expected):
    # The test mesh's seam: a conflicting inherited count is replaced, not kept.
    monkeypatch.setenv("XLA_FLAGS", inherited)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert platform.force_platform("cpu", n_host_devices=8)
    assert os.environ["XLA_FLAGS"] == expected


def test_no_code_overrides_the_placed_cache():
    # One helper decides the directory; nothing else sets or clears it.
    setters = [
        str(path.relative_to(REPO))
        for root in ("rapid_tpu", "tools", "examples")
        for path in (REPO / root).rglob("*.py")
        if "jax_compilation_cache_dir" in path.read_text()
    ] + [
        name for name in ("bench.py", "chip_smoke.py", "__graft_entry__.py")
        if "jax_compilation_cache_dir" in (REPO / name).read_text()
    ]
    assert setters == ["rapid_tpu/utils/platform.py"]


# ---------------------------------------------------------------------------
# The native library is built from the source on disk, never trusted
# ---------------------------------------------------------------------------


@pytest.fixture
def native_copy(tmp_path, monkeypatch):
    """A private copy of native/ (source + Makefile, no build) that
    ``_native`` is pointed at for the test."""
    work = tmp_path / "native"
    work.mkdir()
    for name in ("Makefile", "rapid_native.cpp"):
        shutil.copy(REPO / "native" / name, work / name)
    monkeypatch.setattr(_native, "_REPO_NATIVE_DIR", work)
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_attempted", False)
    monkeypatch.delenv("RAPID_TPU_NO_NATIVE", raising=False)
    return work


needs_toolchain = pytest.mark.skipif(
    shutil.which("make") is None or shutil.which("g++") is None,
    reason="no native toolchain",
)


def _loaded_path() -> str:
    return _native.get_lib()._name


@needs_toolchain
def test_ensure_built_builds_a_clean_checkout(native_copy):
    assert not (native_copy / "build").exists()
    assert _native.ensure_built() is True
    assert _loaded_path() == str(_native._lib_path())
    assert _native.native_xxh64(b"rapid", 0) is not None


@needs_toolchain
def test_ensure_built_replaces_a_stale_library(native_copy):
    # What a copied tree carries: binaries of unknown origin, newer than the
    # source (mtime-based make alone would keep them). Neither is loaded,
    # both are cleared, and the library of THIS source is built.
    build = native_copy / "build"
    build.mkdir()
    stale = [build / "librapid_native.so", build / "librapid_native-0123456789abcdef.so"]
    future = (native_copy / "rapid_native.cpp").stat().st_mtime + 3600
    for path in stale:
        path.write_bytes(b"not a library")
        os.utime(path, (future, future))
    assert _native.get_lib() is None  # never trusted, even before a build
    assert _native.ensure_built() is True
    assert not any(path.exists() for path in stale)
    assert _loaded_path() == str(_native._lib_path())
    assert _native._lib_path().read_bytes()[:4] == b"\x7fELF"


@needs_toolchain
def test_ensure_built_rebuilds_when_the_source_changes(native_copy):
    assert _native.ensure_built() is True
    before = _native._lib_path()
    os.utime(before, (2_000_000_000, 2_000_000_000))  # looks newer than any edit
    source = native_copy / "rapid_native.cpp"
    source.write_text(source.read_text() + "\n// edited\n")
    assert _native._lib_path() != before
    assert _native.ensure_built() is True
    assert _native._lib_path().exists() and not before.exists()
    assert _loaded_path() == str(_native._lib_path())


def test_no_native_switch_skips_the_build(native_copy, monkeypatch):
    monkeypatch.setenv("RAPID_TPU_NO_NATIVE", "1")
    assert _native.ensure_built() is False
    assert not (native_copy / "build").exists()
    assert _native.get_lib() is None


def test_failed_build_falls_back_to_the_python_twin(native_copy):
    stale = native_copy / "build" / "librapid_native.so"
    stale.parent.mkdir()
    stale.write_bytes(b"stale")
    (native_copy / "rapid_native.cpp").write_text("this is not C++\n")
    assert _native.ensure_built() is False
    assert _native.get_lib() is None


# ---------------------------------------------------------------------------
# No fallback hides the kernel
# ---------------------------------------------------------------------------


def test_asking_for_the_kernel_off_tpu_raises_instead_of_falling_back():
    # The engine calls the kernel whenever use_pallas is set (there is no
    # usability probe and no soft import left to hide behind), and the kernel
    # off-TPU surfaces the compiler's own refusal rather than another path.
    import jax.numpy as jnp

    from rapid_tpu.ops import pallas_kernels

    assert not hasattr(pallas_kernels, "_HAS_PALLAS")
    with pytest.raises(Exception, match="(?i)pallas|mosaic|interpret"):
        pallas_kernels.delivery_new_bits_pallas(
            jnp.zeros((3, 256), jnp.uint32), jnp.full((3, 256), 9, jnp.int32),
            jnp.zeros((1,), jnp.uint32), 3, 2, 1000,
        )


# ---------------------------------------------------------------------------
# The two subprocess runs (see cpu_runs), read last
# ---------------------------------------------------------------------------


def test_script_refuses_a_non_tpu_platform(cpu_runs):
    out, err = cpu_runs["script"].communicate(timeout=120)
    assert cpu_runs["script"].returncode != 0
    assert "platform is 'cpu', not 'tpu'" in err
    # The device is named first; no result line follows.
    assert out.startswith("chip_smoke: jax=")
    assert "platform=cpu" in out and '"ok"' not in out


def test_smoke_body_runs_tiny_on_cpu(cpu_runs):
    # jnp core passed explicitly; the kernel twin and the trace are skipped
    # by argument (the first needs Mosaic, the second a device plane). The
    # body itself requires that its warm-up really compiled.
    out, err = cpu_runs["body"].communicate(timeout=240)
    assert cpu_runs["body"].returncode == 0, err[-2000:]
    for stage in ("churn_warmup", "churn_repeats", "crash_xl", "sharded_xl"):
        assert f"stage {stage} ok" in out
    assert "repeats=2 compiles=0" in out  # the repeat window
    assert "stage churn_twin" not in out and "stage trace" not in out
    assert int(out.split("TOTAL_COMPILES")[1]) > 0
