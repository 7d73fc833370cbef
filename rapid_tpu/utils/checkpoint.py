"""Checkpoint / resume.

The reference persists nothing; its serializable state is exactly the
``Configuration`` — (identifiers-seen, ring-0 member list) — documented as
sufficient to reconstruct an identical view (``MembershipView.java:521-533``)
and streamed to every joiner. This module makes that durable:

- host path: ``Configuration`` <-> bytes (the wire codec's field layout), so a
  node can restart into a known view and rejoin from peers;
- device path: the whole ``EngineState`` <-> one ``.npz`` file, so a 100K-node
  virtual cluster resumes mid-protocol (reports, votes, FD counters intact);
- serving path: :func:`save_serving_state` / :func:`load_serving_state` — one
  crash-consistent checkpoint of a whole serving target (state + faults, and
  for fleet-stacked targets the per-tenant knob lanes) plus a JSON meta block
  (the supervisor's wave cursor, rapid_tpu/serving/recovery.py).

Durability discipline (every writer here): the payload is sealed with an
xxh64 integrity trailer (the in-tree ``utils/xxhash.py``) and published by
atomic tmp-file + ``os.replace`` — a reader never observes a half-written
file, and a torn/bit-flipped/truncated one fails loudly as
:class:`CheckpointCorruptError` (a named error the recovery tier can fall
back on) instead of a numpy/zipfile/struct traceback. Pre-trailer
checkpoints still load (the trailer is detected, never assumed).
"""

from __future__ import annotations

import io
import json
import logging
import os
import struct
from pathlib import Path
from typing import TYPE_CHECKING, Any, Dict, Optional, Tuple

import numpy as np

from rapid_tpu.utils.xxhash import xxh64

LOG = logging.getLogger(__name__)


class CheckpointCorruptError(ValueError):
    """A checkpoint file failed its framing or integrity checks (truncated,
    bit-flipped, bad magic, or an unreadable archive). Subclasses ValueError
    so pre-hardening callers that caught ValueError keep working; the
    recovery tier catches THIS name to fall back to an older checkpoint."""


#: Integrity trailer: payload || 8-byte LE xxh64(payload) || magic.
_TRAILER_MAGIC = b"RTXS"
_TRAILER_LEN = 8 + len(_TRAILER_MAGIC)


def _seal(payload: bytes) -> bytes:
    return payload + struct.pack("<Q", xxh64(payload)) + _TRAILER_MAGIC


def _unseal(data: bytes, path) -> bytes:
    """Verify and strip the integrity trailer. Files from pre-trailer
    writers (no magic) pass through unverified — backward compatible, and a
    truncation that happens to cut the trailer off cleanly still fails
    downstream on the archive framing."""
    if len(data) >= _TRAILER_LEN and data[-len(_TRAILER_MAGIC):] == _TRAILER_MAGIC:
        payload = data[:-_TRAILER_LEN]
        (digest,) = struct.unpack("<Q", data[-_TRAILER_LEN:-len(_TRAILER_MAGIC)])
        if xxh64(payload) != digest:
            raise CheckpointCorruptError(
                f"{path}: checkpoint integrity trailer mismatch (the file "
                f"was corrupted after it was written)"
            )
        return payload
    return data


def _atomic_write(path, data: bytes) -> None:
    """Publish ``data`` at ``path`` via tmp-file + rename: a crash mid-write
    leaves the previous checkpoint intact, never a half-written file under
    the published name."""
    path = os.fspath(path)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _default_retired(cfg):
    import jax.numpy as jnp

    LOG.warning(
        "checkpoint predates the 'retired' field: retirement history is "
        "unrecoverable — do not re-admit previously-removed slots after "
        "this resume"
    )
    return jnp.zeros((cfg.n,), dtype=bool)

from rapid_tpu.messaging.codec import (
    Reader,
    Writer,
    read_endpoint,
    read_node_id,
    write_endpoint,
    write_node_id,
)
from rapid_tpu.protocol.view import (
    TOPOLOGY_JAVA,
    TOPOLOGY_NATIVE,
    Configuration,
    MembershipView,
)

if TYPE_CHECKING:
    from rapid_tpu.models.state import EngineConfig, EngineState

_MAGIC = b"RTCF"
# v2 appends a topology-mode byte; v1 checkpoints (which predate the
# java-compat mode and were always native) still load. Native configs are
# WRITTEN as v1: the trailing byte buys nothing in the default case, and
# emitting v2 would make every checkpoint unreadable to older readers that
# only accept v1 — forward incompatibility reserved for java-mode configs,
# which older readers could not resume correctly anyway.
_VERSION = 2
_TOPOLOGY_CODES = {TOPOLOGY_NATIVE: 0, TOPOLOGY_JAVA: 1}
_TOPOLOGY_NAMES = {code: name for name, code in _TOPOLOGY_CODES.items()}


def configuration_to_bytes(config: Configuration) -> bytes:
    w = Writer()
    w.raw(_MAGIC)
    version = 1 if config.topology == TOPOLOGY_NATIVE else _VERSION
    w.u8(version)
    w.u32(len(config.node_ids))
    for nid in config.node_ids:
        write_node_id(w, nid)
    w.u32(len(config.endpoints))
    for ep in config.endpoints:
        write_endpoint(w, ep)
    if version >= 2:
        w.u8(_TOPOLOGY_CODES[config.topology])
    return w.getvalue()


def configuration_from_bytes(data: bytes) -> Configuration:
    if data[:4] != _MAGIC:
        raise CheckpointCorruptError("not a rapid_tpu configuration checkpoint")
    r = Reader(data[4:])
    try:
        version = r.u8()
        if version not in (1, _VERSION):
            raise ValueError(f"unsupported checkpoint version {version}")
        node_ids = tuple(read_node_id(r) for _ in range(r.u32()))
        endpoints = tuple(read_endpoint(r) for _ in range(r.u32()))
        if version == 1:
            topology = TOPOLOGY_NATIVE
        else:
            code = r.u8()
            if code not in _TOPOLOGY_NAMES:
                raise ValueError(f"unknown topology code {code} in checkpoint")
            topology = _TOPOLOGY_NAMES[code]
    except CheckpointCorruptError:
        raise
    except (struct.error, IndexError, ValueError, EOFError) as exc:
        # A truncated/bit-flipped blob must surface as the NAMED error, not
        # a struct/codec traceback — the recovery tier dispatches on it.
        raise CheckpointCorruptError(
            f"truncated or corrupt configuration checkpoint: {exc}"
        ) from exc
    return Configuration(node_ids, endpoints, topology=topology)


def save_configuration(path, config: Configuration) -> None:
    """Durable twin of :func:`configuration_to_bytes`: xxh64-sealed payload
    published by atomic tmp+rename."""
    _atomic_write(path, _seal(configuration_to_bytes(config)))


def load_configuration(path) -> Configuration:
    """Load a :func:`save_configuration` file (or a raw pre-trailer blob);
    truncation/corruption raises :class:`CheckpointCorruptError`."""
    return configuration_from_bytes(_unseal(Path(path).read_bytes(), path))


def view_from_configuration(config: Configuration, k: int) -> MembershipView:
    """Resume: rebuild the K rings from a configuration snapshot (the
    snapshot's topology mode rides along, so a java-compat cluster resumes
    java-compat)."""
    return MembershipView(
        k,
        node_ids=config.node_ids,
        endpoints=config.endpoints,
        topology=config.topology,
    )


def _cfg_entries(cfg: "EngineConfig") -> Dict[str, np.ndarray]:
    return {
        "__cfg__": np.asarray(list(cfg), dtype=np.int64),
        # Field names pin value->field pairing across EngineConfig schema
        # changes: positional loading silently misassigns values once any
        # non-trailing field is added/removed.
        "__cfg_fields__": np.asarray(cfg._fields, dtype=np.str_),
    }


def _npz_bytes(entries: Dict[str, np.ndarray]) -> bytes:
    buf = io.BytesIO()
    np.savez_compressed(buf, **entries)
    return buf.getvalue()


class _LoadedNpz(dict):
    """A fully-materialized checkpoint archive, quacking like the NpzFile
    the loaders were written against (mapping + ``.files`` + a no-op
    context manager — every member is already decompressed in memory)."""

    @property
    def files(self):
        return list(self)

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        return False


def _open_npz(path) -> _LoadedNpz:
    """Read, integrity-check, and FULLY load a sealed .npz checkpoint;
    every corruption class surfaces as :class:`CheckpointCorruptError`,
    never a zipfile/zlib/numpy traceback. Members are decompressed eagerly
    here — member corruption under an intact central directory (a
    trailer-less legacy file, or damage confined to the trailer bytes that
    :func:`_unseal` passes through unverified) only manifests at
    decompression, and deferring it would leak a raw ``zlib.error``
    through the recovery tier's named-error fallback."""
    import zipfile
    import zlib

    payload = _unseal(Path(path).read_bytes(), path)
    try:
        with np.load(io.BytesIO(payload)) as data:
            return _LoadedNpz({k: data[k] for k in data.files})
    except (
        zipfile.BadZipFile, zlib.error, ValueError, OSError, EOFError,
        KeyError,
    ) as exc:
        raise CheckpointCorruptError(
            f"{path}: truncated or corrupt checkpoint archive: {exc}"
        ) from exc


#: EngineState lanes that are functions of other lanes (``ring_perm`` and
#: its inverse of the keys, ``ring_alive`` of ``ring_perm`` and ``alive``):
#: never written by :func:`save_engine_state`, always recomputed on load,
#: in this order.
_DERIVED_LANES = ("ring_perm", "ring_pos", "ring_alive")

#: The one of them a serving checkpoint leaves out as well: it holds the
#: perms and the membership at their stored shapes, and the lane is what
#: the one says of the other. No writer ever wrote it, so every archive
#: loads alike.
_SERVING_DERIVED_LANES = ("ring_alive",)


def save_engine_state(path, cfg: "EngineConfig", state: "EngineState") -> None:
    arrays = {field: np.asarray(value) for field, value in state._asdict().items()}
    # Derived data is never persisted: ring_perm and its inverse ring_pos
    # are pure functions of the key lanes (and ring_alive of ring_perm and
    # alive), and loading a stale/corrupted copy would silently diverge
    # topology from the keys. Load always recomputes them (one sort, one
    # scatter and one gather a ring).
    for derived in _DERIVED_LANES:
        arrays.pop(derived, None)
    _atomic_write(path, _seal(_npz_bytes({**_cfg_entries(cfg), **arrays})))


def load_engine_state(path) -> Tuple["EngineConfig", "EngineState"]:
    from rapid_tpu.models.state import (
        EngineConfig,
        EngineState,
        compaction_policy,
        lane_dtypes,
        with_observer_table_rebuilt,
    )

    with _open_npz(path) as data:
        vals = [int(v) for v in data["__cfg__"]]
        if "__cfg_fields__" in data:
            # Name-keyed: removed fields' saved values are dropped, fields
            # added since the checkpoint fill from EngineConfig defaults.
            saved = dict(zip([str(f) for f in data["__cfg_fields__"]], vals))
            cfg = EngineConfig(**{
                f: saved[f] for f in EngineConfig._fields if f in saved
            })
        else:
            # Legacy checkpoints (no name map, written round <= 2): values
            # are positional over the 12 pre-round-3 fields, optionally
            # followed by the since-deleted pallas_watermark — never by any
            # round-3+ field (those writers always emit the name map). So:
            # take the stable 12, drop the stale tail, default the rest.
            legacy_fields = 12  # ... through delivery_prob_permille
            cfg = EngineConfig(*vals[:legacy_fields])
        import jax.numpy as jnp

        from rapid_tpu.ops.rings import ring_perms as _ring_perms

        # Fields added after a checkpoint was written fill with their
        # initial-state defaults (per-configuration state is safe to reset:
        # at worst a fallback restarts from round 2) — at the POLICY dtypes
        # of the saved config, so a compact checkpoint's filled lanes match
        # the lanes the engine would have built (models/state
        # compaction_policy; wide configs keep the historical int32s).
        dts = {f: jnp.dtype(d) for f, d in lane_dtypes(cfg).items()}
        fire_never = compaction_policy(cfg).fire_never
        defaults = {
            "cp_rnd_r": lambda: jnp.zeros((cfg.n,), dtype=dts["cp_rnd_r"]),
            "cp_rnd_i": lambda: jnp.zeros((cfg.n,), dtype=dts["cp_rnd_i"]),
            "cp_vrnd_r": lambda: jnp.zeros((cfg.n,), dtype=dts["cp_vrnd_r"]),
            "cp_vrnd_i": lambda: jnp.zeros((cfg.n,), dtype=dts["cp_vrnd_i"]),
            "cp_vval_src": lambda: jnp.full(
                (cfg.n,), -1, dtype=dts["cp_vval_src"]
            ),
            "classic_epoch": lambda: jnp.zeros((), dtype=dts["classic_epoch"]),
            "fire_round": lambda: jnp.where(
                jnp.asarray(data["fd_fired"]),
                jnp.zeros((), dtype=dts["fire_round"]),
                jnp.asarray(fire_never, dtype=dts["fire_round"]),
            ),
            "round_idx": lambda: jnp.int32(0),
            "fd_hist": lambda: jnp.zeros((cfg.n, cfg.k), dtype=dts["fd_hist"]),
            # NOT per-configuration state: retirement is cross-configuration
            # history and cannot be reconstructed from an old checkpoint.
            # Resuming one forgets which identity lanes were spent — callers
            # must not re-admit previously-removed slots after such a resume
            # (warned below).
            "retired": lambda: _default_retired(cfg),
            # Derived, not stateful: recompute from the (always-saved) key
            # lanes for checkpoints written before the field existed.
            "ring_perm": lambda: _ring_perms(
                jnp.asarray(data["key_hi"]), jnp.asarray(data["key_lo"])
            ).astype(dts["ring_perm"]),
            # ... and its inverse from it (EngineState lists it after).
            "ring_pos": lambda: _ring_positions_of(arrays["ring_perm"]),
            # ... and liveness by ring position from the perms and the
            # (always-saved) membership.
            "ring_alive": lambda: _ring_liveness_of(
                arrays["ring_perm"], jnp.asarray(data["alive"])
            ),
        }
        arrays = {}
        for field in EngineState._fields:
            if field in _DERIVED_LANES:
                # Always derived from the key lanes — a persisted copy (from
                # any writer) is ignored rather than trusted for coherence.
                arrays[field] = defaults[field]()
            elif field in data:
                arrays[field] = jnp.asarray(data[field])
            elif field in defaults:
                arrays[field] = defaults[field]()
            else:
                raise KeyError(
                    f"checkpoint missing field {field!r} with no known default"
                )
        state = with_observer_table_rebuilt(EngineState(**arrays))
    return cfg, state


# ---------------------------------------------------------------------------
# Serving checkpoints: the whole serving target (state + faults [+ knobs]),
# wide / compact / bit-packed / fleet-stacked alike, plus a meta cursor
# ---------------------------------------------------------------------------

def save_serving_state(
    path,
    cfg: "EngineConfig",
    state: "EngineState",
    faults,
    knobs=None,
    meta: Optional[Dict[str, Any]] = None,
    links=None,
) -> None:
    """One crash-consistent checkpoint of a serving target: the state AND
    fault pytrees (and, for a fleet, the [t] knob lanes) exactly as stored —
    shapes and dtypes round-trip verbatim, so compact (policy-narrowed),
    bit-packed, and fleet-stacked layouts all come back bit-identical
    (unlike :func:`save_engine_state`, ``ring_perm`` is persisted too: the
    stacked/packed shapes cannot be re-derived by the single-cluster
    recompute, and bit-exact resume is the whole point here; ``ring_alive``
    alone is left out and rebuilt from the two lanes it is a function of).
    ``meta`` is a
    small JSON-serializable dict (the supervisor's wave cursor). ``links`` is
    the target's link-fault lane where one is set (``VirtualCluster.links``,
    or ``TenantFleet.links`` with its leading tenant axis);
    :func:`load_link_faults` reads it back at its saved shapes. Sealed +
    atomic like every writer in this module."""
    entries = dict(_cfg_entries(cfg))
    entries["__meta__"] = np.frombuffer(
        json.dumps(meta or {}, sort_keys=True).encode(), dtype=np.uint8
    )
    for prefix, tree in (
        ("state", state), ("faults", faults), ("knobs", knobs), ("links", links),
    ):
        if tree is None:
            continue
        for field, value in tree._asdict().items():
            if prefix == "state" and field in _SERVING_DERIVED_LANES:
                continue
            entries[f"{prefix}__{field}"] = np.asarray(value)
    _atomic_write(path, _seal(_npz_bytes(entries)))


def load_serving_state(path):
    """Inverse of :func:`save_serving_state`: returns ``(cfg, state, faults,
    knobs_or_None, meta)`` with every leaf at its saved shape and dtype.
    Corruption raises :class:`CheckpointCorruptError`; a missing pytree
    field raises KeyError naming it (a serving checkpoint is always written
    whole by this module — absence means a foreign or damaged file)."""
    import jax.numpy as jnp

    from rapid_tpu.models.state import (
        EngineConfig,
        EngineState,
        FaultInputs,
        with_observer_table_rebuilt,
    )

    with _open_npz(path) as data:
        vals = [int(v) for v in data["__cfg__"]]
        saved = dict(zip([str(f) for f in data["__cfg_fields__"]], vals))
        cfg = EngineConfig(**{
            f: saved[f] for f in EngineConfig._fields if f in saved
        })
        meta = json.loads(bytes(data["__meta__"]).decode() or "{}")

        def tree(cls, prefix):
            arrays = {}
            for field in cls._fields:
                key = f"{prefix}__{field}"
                if prefix == "state" and field in _SERVING_DERIVED_LANES:
                    arrays[field] = _ring_liveness_of(
                        arrays["ring_perm"], jnp.asarray(data["state__alive"])
                    )
                    continue
                if key == "state__ring_pos" and key not in data:
                    # A writer older than the lane: it is the inverse of the
                    # perms the archive does hold, a tenant at a time.
                    arrays[field] = _ring_positions_of(arrays["ring_perm"])
                    continue
                if key not in data:
                    raise KeyError(
                        f"serving checkpoint missing {key!r} (not written "
                        f"by save_serving_state, or damaged)"
                    )
                arrays[field] = jnp.asarray(data[key])
            return cls(**arrays)

        state = with_observer_table_rebuilt(tree(EngineState, "state"))
        faults = tree(FaultInputs, "faults")
        knobs = None
        if any(k.startswith("knobs__") for k in data.files):
            from rapid_tpu.tenancy.fleet import TenantKnobs

            knobs = tree(TenantKnobs, "knobs")
    return cfg, state, faults, knobs, meta


def _ring_positions_of(perm):
    """``ring_positions`` of a cluster's ``[k, n]`` perms or a fleet's
    stacked ``[t, k, n]``."""
    import jax

    from rapid_tpu.ops.rings import ring_positions

    return ring_positions(perm) if perm.ndim == 2 else jax.vmap(ring_positions)(perm)


def _ring_liveness_of(perm, alive):
    """``ring_liveness`` of a cluster's ``[k, n]`` perms and ``[n]``
    membership or a fleet's stacked ones; a bit-packed membership
    (``models/state.pack_masks``) is unpacked for the look-up."""
    import jax

    from rapid_tpu.models.state import unpack_bool
    from rapid_tpu.ops.rings import ring_liveness

    if alive.dtype != bool:
        alive = unpack_bool(alive)
    return ring_liveness(perm, alive) if perm.ndim == 2 else jax.vmap(ring_liveness)(perm, alive)


def load_link_faults(path):
    """The link-fault lane of a serving checkpoint, or ``None`` where the
    archive holds none (a cluster or a fleet that had none set, or a writer
    older than the lane): such a target resumes unset. Reads the lane's
    members only; :func:`load_serving_state` returns what it always did."""
    import jax.numpy as jnp

    from rapid_tpu.models.state import LinkFaults

    with _open_npz(path) as data:
        keys = {field: f"links__{field}" for field in LinkFaults._fields}
        missing = sorted(field for field, key in keys.items() if key not in data)
        if len(missing) == len(keys):
            return None
        if missing:
            raise KeyError(f"serving checkpoint holds a link-fault lane without {missing}")
        return LinkFaults(**{field: jnp.asarray(data[key]) for field, key in keys.items()})
