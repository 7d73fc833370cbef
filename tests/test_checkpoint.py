"""Checkpoint/resume: host configuration snapshots and engine state —
including the ISSUE-15 durability bar: atomic publishes, xxh64 integrity
trailers, every corruption class a NAMED CheckpointCorruptError (never a
numpy/zipfile/struct traceback), and bit-exact round trips for the
compact, bit-packed, and fleet-stacked layouts the serving supervisor
checkpoints."""

import numpy as np
import pytest

from rapid_tpu.protocol.view import MembershipView
from rapid_tpu.types import Endpoint, NodeId
from rapid_tpu.utils.checkpoint import (
    CheckpointCorruptError,
    configuration_from_bytes,
    configuration_to_bytes,
    load_configuration,
    load_engine_state,
    load_serving_state,
    save_configuration,
    save_engine_state,
    save_serving_state,
    view_from_configuration,
)

K = 10


def test_configuration_roundtrip(tmp_path):
    view = MembershipView(K)
    for i in range(40):
        view.ring_add(Endpoint(f"10.3.0.{i}", 4000 + i), NodeId(i, i * 7))
    blob = configuration_to_bytes(view.configuration)
    restored = configuration_from_bytes(blob)
    assert restored.node_ids == view.configuration.node_ids
    assert restored.endpoints == view.configuration.endpoints
    assert restored.configuration_id == view.configuration_id

    # Resume: identical rings and config id.
    resumed = view_from_configuration(restored, K)
    assert resumed.configuration_id == view.configuration_id
    for ring_idx in range(K):
        assert resumed.ring(ring_idx) == view.ring(ring_idx)


def test_configuration_rejects_garbage():
    import pytest

    with pytest.raises(ValueError):
        configuration_from_bytes(b"not a checkpoint")


def test_native_configs_write_v1_java_configs_write_v2():
    # Backward compatibility: the default (native) topology emits the v1
    # layout older readers accept; only java-mode configs — which old readers
    # could not resume correctly anyway — pay the v2 trailing topology byte.
    from rapid_tpu.protocol.view import TOPOLOGY_JAVA

    native = MembershipView(K)
    native.ring_add(Endpoint("10.3.0.1", 4000), NodeId(1, 7))
    native_blob = configuration_to_bytes(native.configuration)
    assert native_blob[4] == 1  # version byte after the 4-byte magic

    java = MembershipView(K, topology=TOPOLOGY_JAVA)
    java.ring_add(Endpoint("10.3.0.1", 4000), NodeId(1, 7))
    java_blob = configuration_to_bytes(java.configuration)
    assert java_blob[4] == 2
    assert len(java_blob) == len(native_blob) + 1  # the trailing topology byte

    for blob, topology in ((native_blob, "native"), (java_blob, TOPOLOGY_JAVA)):
        restored = configuration_from_bytes(blob)
        assert restored.topology == topology


def test_engine_state_roundtrip(tmp_path):
    from rapid_tpu.models.virtual_cluster import VirtualCluster

    vc = VirtualCluster.create(120, fd_threshold=3, seed=0)
    vc.crash([5, 9])
    # Advance mid-protocol so non-trivial state is saved.
    for _ in range(2):
        vc.step()

    path = tmp_path / "engine.npz"
    save_engine_state(path, vc.cfg, vc.state)
    cfg, state = load_engine_state(path)
    assert cfg == vc.cfg
    for field in state._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(state, field)), np.asarray(getattr(vc.state, field)), err_msg=field
        )

    # The resumed cluster continues to the same decision.
    resumed = VirtualCluster(cfg, state)
    resumed.crash([5, 9])
    rounds_resumed, events = resumed.run_until_converged()
    assert events is not None
    rounds_orig, events_orig = vc.run_until_converged()
    assert events_orig is not None
    assert rounds_resumed == rounds_orig
    np.testing.assert_array_equal(resumed.alive_mask, vc.alive_mask)


def test_cluster_metrics_surface():
    import asyncio
    import random

    from rapid_tpu.messaging.inprocess import InProcessNetwork
    from rapid_tpu.monitoring.static_fd import StaticFailureDetectorFactory
    from rapid_tpu.protocol.cluster import Cluster
    from rapid_tpu.settings import Settings
    from rapid_tpu.types import Endpoint

    async def scenario():
        settings = Settings()
        settings.batching_window_ms = 20
        settings.failure_detector_interval_ms = 50
        network = InProcessNetwork()
        fd = StaticFailureDetectorFactory()
        seed = await Cluster.start(Endpoint("127.0.0.1", 31000), settings=settings,
                                   network=network, fd_factory=fd, rng=random.Random(0))
        node = await Cluster.join(Endpoint("127.0.0.1", 31000), Endpoint("127.0.0.1", 31001),
                                  settings=settings, network=network, fd_factory=fd,
                                  rng=random.Random(1))
        for _ in range(200):
            if seed.membership_size == 2 and node.membership_size == 2:
                break
            await asyncio.sleep(0.02)
        metrics = seed.metrics
        await seed.shutdown()
        await node.shutdown()
        return metrics

    metrics = asyncio.run(asyncio.wait_for(scenario(), timeout=30))
    assert metrics["view_changes"] >= 1
    assert metrics["proposals_announced"] >= 1
    assert metrics["alerts_enqueued"] >= 1
    assert "view_change_convergence_ms" in metrics
    assert metrics["view_change_convergence_ms"]["last"] > 0


def test_engine_state_loads_checkpoint_missing_new_fields(tmp_path):
    # Forward compatibility: a checkpoint written before fire_round/round_idx
    # (and the classic-paxos fields) existed must load with safe defaults and
    # still converge. Simulate by deleting those keys from a fresh save.
    import numpy as np

    from rapid_tpu.models.virtual_cluster import VirtualCluster
    from rapid_tpu.utils.checkpoint import load_engine_state, save_engine_state

    vc = VirtualCluster.create(64, fd_threshold=2, seed=3)
    path = tmp_path / "state.npz"
    save_engine_state(path, vc.cfg, vc.state)

    with np.load(path) as data:
        kept = {k: data[k] for k in data.files}
    for legacy_missing in (
        "fire_round", "round_idx", "cp_rnd_r", "cp_rnd_i",
        "cp_vrnd_r", "cp_vrnd_i", "cp_vval_src", "classic_epoch",
        "ring_perm", "ring_pos",  # derived: must backfill from the saved key lanes
    ):
        kept.pop(legacy_missing, None)
    stripped = tmp_path / "legacy.npz"
    np.savez_compressed(stripped, **kept)

    cfg, state = load_engine_state(stripped)
    assert cfg == vc.cfg
    np.testing.assert_array_equal(
        np.asarray(state.ring_perm), np.asarray(vc.state.ring_perm)
    )
    np.testing.assert_array_equal(
        np.asarray(state.ring_pos), np.asarray(vc.state.ring_pos)
    )
    restored = VirtualCluster(cfg, state)
    restored.crash([7])
    rounds, events = restored.run_until_converged(max_steps=32)
    assert events is not None
    assert restored.membership_size == 63


def _small_cluster(compact=False, seed=0):
    from rapid_tpu.models.virtual_cluster import VirtualCluster

    vc = VirtualCluster.create(
        24, n_slots=40, k=3, h=3, l=1, cohorts=2, fd_threshold=2,
        seed=seed, compact=compact,
    )
    vc.assign_cohorts_roundrobin()
    return vc


def _trees_bit_identical(a, b):
    for field in a._fields:
        x = np.asarray(getattr(a, field))
        y = np.asarray(getattr(b, field))
        assert x.dtype == y.dtype and x.shape == y.shape, field
        np.testing.assert_array_equal(x, y, err_msg=field)


# ---------------------------------------------------------------------------
# ISSUE 15 satellite: corruption is a NAMED error, each class pinned
# ---------------------------------------------------------------------------


def test_configuration_file_roundtrip_and_corruption_classes(tmp_path):
    view = MembershipView(K)
    for i in range(8):
        view.ring_add(Endpoint(f"10.3.0.{i}", 4000 + i), NodeId(i, i * 7))
    path = tmp_path / "config.rtcf"
    save_configuration(path, view.configuration)
    assert not list(tmp_path.glob("*.tmp.*"))  # atomic publish, no debris
    restored = load_configuration(path)
    assert restored.configuration_id == view.configuration_id

    data = path.read_bytes()
    # Bit flip inside the payload: the xxh64 trailer catches it by name.
    flipped = bytearray(data)
    flipped[len(flipped) // 3] ^= 0xFF
    (tmp_path / "flip.rtcf").write_bytes(bytes(flipped))
    with pytest.raises(CheckpointCorruptError):
        load_configuration(tmp_path / "flip.rtcf")
    # Truncation (trailer gone, payload cut): named, not a struct error.
    (tmp_path / "trunc.rtcf").write_bytes(data[: len(data) // 2])
    with pytest.raises(CheckpointCorruptError):
        load_configuration(tmp_path / "trunc.rtcf")
    # Bad magic: named.
    (tmp_path / "magic.rtcf").write_bytes(b"XXXX" + data[4:])
    with pytest.raises(CheckpointCorruptError):
        load_configuration(tmp_path / "magic.rtcf")
    # Truncated raw BYTES (pre-file callers) are named too, and the named
    # error still satisfies legacy except-ValueError callers.
    blob = configuration_to_bytes(view.configuration)
    with pytest.raises(CheckpointCorruptError):
        configuration_from_bytes(blob[: len(blob) // 2])
    assert issubclass(CheckpointCorruptError, ValueError)


def test_engine_checkpoint_corruption_classes_are_named(tmp_path):
    vc = _small_cluster()
    vc.crash([3])
    vc.step()
    path = tmp_path / "engine.npz"
    save_engine_state(path, vc.cfg, vc.state)
    assert not list(tmp_path.glob("*.tmp.*"))
    data = path.read_bytes()
    # Truncated archive.
    (tmp_path / "trunc.npz").write_bytes(data[: len(data) // 2])
    with pytest.raises(CheckpointCorruptError):
        load_engine_state(tmp_path / "trunc.npz")
    # Flipped payload byte under an intact length: trailer mismatch.
    flipped = bytearray(data)
    flipped[len(flipped) // 2] ^= 0xFF
    (tmp_path / "flip.npz").write_bytes(bytes(flipped))
    with pytest.raises(CheckpointCorruptError):
        load_engine_state(tmp_path / "flip.npz")
    # Not an archive at all.
    (tmp_path / "garbage.npz").write_bytes(b"not a checkpoint")
    with pytest.raises(CheckpointCorruptError):
        load_engine_state(tmp_path / "garbage.npz")
    # Member corruption under an INTACT central directory (a trailer-less
    # legacy file with a flipped byte mid-archive): the damage only
    # surfaces at member decompression — still the NAMED error, never a
    # raw zlib traceback leaking through the recovery fallback chain.
    legacy_bad = bytearray(data[:-12])
    legacy_bad[len(legacy_bad) // 2] ^= 0xFF
    (tmp_path / "legacy_bad.npz").write_bytes(bytes(legacy_bad))
    with pytest.raises(CheckpointCorruptError):
        load_engine_state(tmp_path / "legacy_bad.npz")
    # Legacy pre-trailer writers (a bare .npz) still load.
    (tmp_path / "legacy.npz").write_bytes(data[:-12])  # strip the trailer
    cfg2, _state2 = load_engine_state(tmp_path / "legacy.npz")
    assert cfg2 == vc.cfg


# ---------------------------------------------------------------------------
# ISSUE 15 satellite: the layouts the supervisor checkpoints round-trip
# bit-exactly (compact, packed, fleet-stacked), and wide checkpoints
# migrate onto a compact config
# ---------------------------------------------------------------------------


def test_packed_mask_layout_roundtrips_bit_identically(tmp_path):
    from rapid_tpu.models.state import pack_masks, unpack_masks

    vc = _small_cluster()
    vc.crash([2, 7])
    vc.step()
    packed_state = pack_masks(vc.state)
    packed_faults = pack_masks(vc.faults)
    path = tmp_path / "packed.npz"
    save_serving_state(
        path, vc.cfg, packed_state, packed_faults, meta={"layout": "packed"}
    )
    cfg2, state2, faults2, knobs2, meta = load_serving_state(path)
    assert cfg2 == vc.cfg and knobs2 is None and meta == {"layout": "packed"}
    _trees_bit_identical(state2, packed_state)  # packed shapes verbatim
    _trees_bit_identical(faults2, packed_faults)
    _trees_bit_identical(unpack_masks(state2), vc.state)  # and exact unpack


def test_compact_serving_checkpoint_widens_bit_identically(tmp_path):
    from rapid_tpu.models.state import widen_state

    vc = _small_cluster(compact=True)
    vc.crash([1, 4])
    vc.run_until_converged(64)
    path = tmp_path / "compact.npz"
    save_serving_state(path, vc.cfg, vc.state, vc.faults)
    cfg2, state2, _faults2, _knobs, _meta = load_serving_state(path)
    assert cfg2.compact == 1
    _trees_bit_identical(state2, vc.state)  # narrow dtypes verbatim
    # ...and the widened view equals the widened original bit-for-bit (the
    # differential seam every compact comparison goes through).
    _trees_bit_identical(widen_state(cfg2, state2), widen_state(vc.cfg, vc.state))


def test_fleet_stacked_checkpoint_roundtrips_and_resumes(tmp_path):
    from rapid_tpu.models.virtual_cluster import VirtualCluster
    from rapid_tpu.tenancy import TenantFleet

    clusters = []
    for i in range(3):
        vc = VirtualCluster.create(
            16, k=3, h=3, l=1, cohorts=2, fd_threshold=2, seed=30 + i
        )
        vc.assign_cohorts_roundrobin()
        clusters.append(vc)
    fleet = TenantFleet.from_clusters(clusters)
    fleet.stream_crash([(0, 2), (2, 5)])
    fleet.step()
    path = tmp_path / "fleet.npz"
    save_serving_state(
        path, fleet.cfg, fleet.state, fleet.faults, knobs=fleet.knobs,
        meta={"wave_index": 1},
    )
    cfg2, state2, faults2, knobs2, meta = load_serving_state(path)
    assert meta["wave_index"] == 1 and knobs2 is not None
    _trees_bit_identical(state2, fleet.state)
    _trees_bit_identical(faults2, fleet.faults)
    _trees_bit_identical(knobs2, fleet.knobs)
    # The resumed fleet steps on to the same place as the original.
    resumed = TenantFleet(cfg2, state2, faults2, knobs2)
    resumed.step()
    fleet.step()
    _trees_bit_identical(resumed.state, fleet.state)
    assert resumed.config_ids() == fleet.config_ids()
    # A missing pytree field is a loud KeyError naming the key.
    import io

    with np.load(io.BytesIO(path.read_bytes()[:-12])) as data:
        kept = {k: data[k] for k in data.files if k != "faults__crashed"}
    buf = io.BytesIO()
    np.savez_compressed(buf, **kept)
    (tmp_path / "missing.npz").write_bytes(buf.getvalue())
    with pytest.raises(KeyError, match="faults__crashed"):
        load_serving_state(tmp_path / "missing.npz")


@pytest.mark.parametrize("target", ["cluster", "compact_cluster", "fleet"])
def test_a_serving_checkpoint_older_than_ring_pos_gets_it_from_its_perms(tmp_path, target):
    """``EngineState.ring_pos`` (PR 49) is the inverse of ``ring_perm``, which
    a serving checkpoint has always held: an archive written before the lane
    loads to the state the engine would have built, a tenant at a time."""
    import io

    if target == "fleet":
        from rapid_tpu.tenancy import TenantFleet

        served = TenantFleet.from_clusters([_small_cluster(seed=s) for s in (5, 6)])
        knobs = served.knobs
    else:
        served, knobs = _small_cluster(compact=target == "compact_cluster"), None
    path = tmp_path / "now.npz"
    save_serving_state(path, served.cfg, served.state, served.faults, knobs=knobs)
    with np.load(io.BytesIO(path.read_bytes()[:-12])) as data:  # less the seal
        kept = {k: data[k] for k in data.files if k != "state__ring_pos"}
    assert len(kept) == len(data.files) - 1
    buf = io.BytesIO()
    np.savez_compressed(buf, **kept)
    (tmp_path / "older.npz").write_bytes(buf.getvalue())
    _cfg, state, _faults, _knobs, _meta = load_serving_state(tmp_path / "older.npz")
    _trees_bit_identical(state, served.state)


@pytest.mark.parametrize("target", ["cluster", "compact_cluster", "packed_cluster", "fleet"])
@pytest.mark.parametrize("archive", ["as_written", "with_a_stale_lane"])
def test_no_checkpoint_holds_ring_alive_and_every_load_rebuilds_it(tmp_path, target, archive):
    """``EngineState.ring_alive`` (PR 50) is what ``ring_perm`` says of
    ``alive``: neither writer stores it, so an archive written before the
    lane is an archive written now, and a load gives the lane the engine
    holds, a tenant at a time, after a view change has moved it, from a
    bit-packed membership too. A file that does hold one (no writer of this
    repo's) has it ignored, as ``ring_perm`` is in an engine checkpoint."""
    import io

    from rapid_tpu.models.state import pack_masks, unpack_masks

    if target == "fleet":
        from rapid_tpu.tenancy import TenantFleet

        served = TenantFleet.from_clusters([_small_cluster(seed=s) for s in (5, 6)])
        served.faults = served.faults._replace(
            crashed=served.faults.crashed.at[0, 3].set(True).at[1, 7].set(True))
        served.run_to_decision(max_steps=32)
        knobs = served.knobs
    else:
        served, knobs = _small_cluster(compact=target == "compact_cluster"), None
        served.crash([3, 7])
        served.run_until_converged(max_steps=32)
    state, faults = served.state, served.faults
    assert not np.asarray(state.ring_alive).all()  # the commit moved the lane
    if target == "packed_cluster":
        state, faults = pack_masks(state), pack_masks(faults)
    writers = [("serving", "state__ring_alive", "state__alive")]
    if target in ("cluster", "compact_cluster"):
        writers.append(("engine", "ring_alive", "alive"))
    for writer, key, alive_key in writers:
        path = tmp_path / f"{writer}.npz"
        if writer == "serving":
            save_serving_state(path, served.cfg, state, faults, knobs=knobs)
        else:
            save_engine_state(path, served.cfg, state)
        with np.load(io.BytesIO(path.read_bytes()[:-12])) as data:  # less the seal
            held = {k: data[k] for k in data.files}
        assert key not in held and alive_key in held
        if archive == "with_a_stale_lane":
            held[key] = ~np.asarray(served.state.ring_alive)
            buf = io.BytesIO()
            np.savez_compressed(buf, **held)
            path.write_bytes(buf.getvalue())
        loaded = load_serving_state(path)[1] if writer == "serving" else load_engine_state(path)[1]
        _trees_bit_identical(loaded, state)
        lane = np.asarray(loaded.ring_alive)
        alive = np.asarray((unpack_masks(loaded) if target == "packed_cluster" else loaded).alive)
        perm = np.asarray(loaded.ring_perm)
        for t in range(alive.size // alive.shape[-1]):
            np.testing.assert_array_equal(
                lane.reshape(-1, *lane.shape[-2:])[t],
                alive.reshape(-1, alive.shape[-1])[t][perm.reshape(-1, *perm.shape[-2:])[t]])


@pytest.mark.parametrize("target", ["cluster", "compact_cluster", "packed_cluster", "fleet"])
@pytest.mark.parametrize("archive", ["as_written", "with_a_wrong_column"])
def test_every_load_makes_inval_obs_whole_off_the_pending_columns(tmp_path, target, archive):
    """A view change REPAIRS ``EngineState.inval_obs`` (PR 52) and derives
    ``obs_idx`` from it, so the lane has to be the walk's table wherever no
    joiner is pending: a load does not take an archive's word for that. After
    a commit and with a joiner placed and still pending, an archive loads to
    the state that was saved, bit for bit; one whose table holds a wrong
    column (no writer of this repo's) loads to that state too, and the
    pending joiner's column keeps the gatekeepers that were saved."""
    import io

    from rapid_tpu.models.state import pack_masks

    joiner = 31
    if target == "fleet":
        from rapid_tpu.tenancy import TenantFleet

        served = TenantFleet.from_clusters([_small_cluster(seed=s) for s in (5, 6)])
        served.faults = served.faults._replace(
            crashed=served.faults.crashed.at[0, 3].set(True).at[1, 7].set(True))
        served.run_to_decision(max_steps=32)
        served.inject_join_wave([(0, joiner), (1, joiner)])
        knobs = served.knobs
    else:
        served, knobs = _small_cluster(compact=target == "compact_cluster"), None
        served.crash([3, 7])
        served.run_until_converged(max_steps=32)
        served.inject_join_wave([joiner])
    state, faults = served.state, served.faults
    assert np.asarray(state.join_pending)[..., joiner].all()
    assert (np.asarray(state.inval_obs)[..., joiner] >= 0).all()  # its gatekeepers
    if target == "packed_cluster":
        state, faults = pack_masks(state), pack_masks(faults)
    writers = [("serving", "state__inval_obs")]
    if target in ("cluster", "compact_cluster"):
        writers.append(("engine", "inval_obs"))
    for writer, key in writers:
        path = tmp_path / f"{writer}.npz"
        if writer == "serving":
            save_serving_state(path, served.cfg, state, faults, knobs=knobs)
        else:
            save_engine_state(path, served.cfg, state)
        if archive == "with_a_wrong_column":
            with np.load(io.BytesIO(path.read_bytes()[:-12])) as data:  # less the seal
                held = {k: data[k] for k in data.files}
            wrong = held[key].copy()
            wrong[..., 5] = wrong[..., 6]  # a member's column: somebody else's observers
            wrong[..., 3] = 0  # a removed member's: no longer -1
            assert (wrong != held[key]).any()
            held[key] = wrong
            buf = io.BytesIO()
            np.savez_compressed(buf, **held)
            path.write_bytes(buf.getvalue())
        loaded = load_serving_state(path)[1] if writer == "serving" else load_engine_state(path)[1]
        _trees_bit_identical(loaded, state)


def test_wide_checkpoint_loads_under_a_compact_config(tmp_path):
    """Migration path: a checkpoint written by a WIDE deployment is brought
    up compact — validate the envelope, narrow, and the widened view is
    bit-identical to the original (so the compact resume replays the wide
    run's protocol exactly); the migrated cluster keeps converging."""
    from rapid_tpu.models.state import narrow_state, validate_envelope, widen_state
    from rapid_tpu.models.virtual_cluster import VirtualCluster

    vc = _small_cluster(compact=False)
    vc.crash([2, 9])
    vc.step()
    path = tmp_path / "wide.npz"
    save_engine_state(path, vc.cfg, vc.state)
    cfg_w, state_w = load_engine_state(path)
    assert cfg_w.compact == 0
    cfg_c = cfg_w._replace(compact=1)
    validate_envelope(cfg_c, state_w)  # the loud alternative to a wrapping cast
    narrowed = narrow_state(cfg_c, state_w)
    _trees_bit_identical(widen_state(cfg_c, narrowed), state_w)
    migrated = VirtualCluster(cfg_c, narrowed)
    migrated.crash([2, 9])
    rounds, events = migrated.run_until_converged(64)
    assert events is not None
    assert migrated.membership_size == 22


def test_legacy_positional_config_drops_stale_watermark_value(tmp_path):
    # Round-<=2 checkpoints carry no __cfg_fields__ name map: 12 positional
    # values plus (sometimes) the since-deleted pallas_watermark. The legacy
    # branch must truncate to the stable 12 and default the rest — NOT let
    # the stale 13th value load as pallas_lanes (lanes=1 would then blow up
    # the delivery kernel's multiple-of-128 check at call time).
    from rapid_tpu.models.state import EngineConfig
    from rapid_tpu.models.virtual_cluster import VirtualCluster

    vc = VirtualCluster.create(32, fd_threshold=2, seed=4, delivery_spread=1)
    path = tmp_path / "state.npz"
    save_engine_state(path, vc.cfg, vc.state)

    with np.load(path) as data:
        kept = {k: data[k] for k in data.files}
    del kept["__cfg_fields__"]  # legacy writer had no name map...
    legacy_vals = [int(v) for v in kept["__cfg__"]][:12]
    legacy_vals.append(1)  # ...and a trailing pallas_watermark=1
    kept["__cfg__"] = np.asarray(legacy_vals, dtype=np.int64)
    legacy = tmp_path / "legacy_cfg.npz"
    np.savez_compressed(legacy, **kept)

    cfg, state = load_engine_state(legacy)
    assert cfg.pallas_lanes == EngineConfig._field_defaults["pallas_lanes"] == 128
    assert cfg._replace(pallas_lanes=vc.cfg.pallas_lanes) == vc.cfg
    restored = VirtualCluster(cfg, state)
    restored.crash([3])
    rounds, events = restored.run_until_converged(max_steps=32)
    assert events is not None
    assert restored.membership_size == 31
