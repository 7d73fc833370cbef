"""``_edge_masks`` against the two-gather body it replaced, bit for bit.

The mask build looks the observer of every (subject, ring) edge up ONCE, in
a per-member table that holds the packed ``rx_block`` words and, in a row
of its own, the observer's ``active`` bit (no word lends a bit, whether the
cohort count fills its words or not). Nothing that leaves the function may
change, so the body as it stood
before is kept here verbatim as the oracle and both outputs are held to it
at cohort counts on every side of a word boundary: eagerly, under ``jit``,
under the fleet's tenant ``vmap``, and in the taken arm of the carried
step's view-change gate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rapid_tpu.models import virtual_cluster as vcm
from rapid_tpu.models.state import FaultInputs
from rapid_tpu.tenancy.fleet import fleet_edge_masks_impl

COHORTS = [1, 8, 31, 32, 33, 64]
SLOTS, MEMBERS, TENANTS = 48, 40, 3


def reference_edge_masks(cfg, state, faults):
    """The body of ``_edge_masks`` as it stood with two gathers an edge."""
    n, k, c = cfg.n, cfg.k, cfg.c
    w = vcm.cohort_words(c)
    obs = state.obs_idx.T  # [n, k] — observer of (subject s, ring k)
    obs_clamped = jnp.clip(obs, 0, n - 1)

    active = state.alive & ~faults.crashed
    observer_active = (obs >= 0) & active[obs_clamped]

    # Pack rx_block over the cohort axis, then gather per observer.
    pad = w * 32 - c
    rxb = jnp.pad(faults.rx_block, ((0, pad), (0, 0))).astype(jnp.uint32)  # [32w, n]
    rxb = rxb.reshape(w, 32, n)
    bit_weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    words = jnp.sum(rxb * bit_weights[None, :, None], axis=1, dtype=jnp.uint32)  # [w, n]
    blocked_rows = words[:, obs_clamped.T].reshape(w * k, n)  # THE gather
    return observer_active, blocked_rows


def cluster(c, seed=0):
    vc = vcm.VirtualCluster.create(
        MEMBERS, n_slots=SLOTS, k=3, h=3, l=1, cohorts=c, fd_threshold=2, seed=seed)
    vc.assign_cohorts_roundrobin()
    return vc


def scrambled(vc, seed):
    """The cluster's own topology under every input the build must survive:
    random ``rx_block``, crashed observers, dead observers (slots the table
    still names), edges with no observer (``-1``: whole subjects, and the
    whole table of a ring, as with fewer than two alive)."""
    rng = np.random.default_rng(seed)
    cfg, state = vc.cfg, vc.state
    alive = np.array(state.alive)
    alive[rng.choice(MEMBERS, 5, replace=False)] = False
    obs_idx = np.array(state.obs_idx)
    obs_idx[:, rng.choice(cfg.n, 6, replace=False)] = -1
    obs_idx[rng.integers(cfg.k)] = -1
    state = state._replace(
        alive=jnp.asarray(alive), obs_idx=jnp.asarray(obs_idx, dtype=state.obs_idx.dtype))
    faults = FaultInputs(
        crashed=jnp.asarray(rng.random(cfg.n) < 0.2),
        probe_fail=vc.faults.probe_fail,
        rx_block=jnp.asarray(rng.random((cfg.c, cfg.n)) < 0.4),
    )
    return cfg, state, faults


def assert_same(ours, theirs, where):
    for name, one, other in zip(("observer_active", "blocked_rows"), ours, theirs):
        assert one.dtype == other.dtype and one.shape == other.shape, (where, name)
        np.testing.assert_array_equal(np.asarray(one), np.asarray(other), err_msg=f"{where}: {name}")


def _eager(c):
    cfg, state, faults = scrambled(cluster(c), seed=c)
    ours = vcm._edge_masks(cfg, state, faults)
    assert ours[0].shape == (cfg.n, cfg.k) and ours[0].dtype == jnp.bool_
    assert ours[1].shape == (vcm.cohort_words(c) * cfg.k, cfg.n) and ours[1].dtype == jnp.uint32
    theirs = reference_edge_masks(cfg, state, faults)
    # the scenario has what it says: edges without an observer, observers
    # that are dead or crashed, blocked cohorts in the last word's top bit
    assert (np.asarray(state.obs_idx) < 0).any() and np.asarray(theirs[0]).any()
    assert not np.asarray(theirs[0]).all()
    assert (np.asarray(theirs[1][-cfg.k:]) >> ((c - 1) % 32) & 1).any()
    assert_same(ours, theirs, f"eager c={c}")


def _jit(c):
    cfg, state, faults = scrambled(cluster(c), seed=100 + c)
    assert_same(
        vcm.edge_masks_build(cfg, state, faults),
        jax.jit(reference_edge_masks, static_argnums=(0,))(cfg, state, faults), f"jit c={c}")


def _vmap(c):
    tenants = [scrambled(cluster(c, seed=t), seed=200 + 7 * c + t) for t in range(TENANTS)]
    cfg = tenants[0][0]
    state, faults = (
        jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *(tenant[i] for tenant in tenants))
        for i in (1, 2))
    ours = jax.jit(fleet_edge_masks_impl, static_argnums=(0,))(cfg, state, faults)
    theirs = jax.vmap(lambda s, f: reference_edge_masks(cfg, s, f))(state, faults)
    assert ours[0].shape == (TENANTS, cfg.n, cfg.k)
    assert_same(ours, theirs, f"vmap c={c}")


def _carried_step(c):
    """One ``engine_step_carried_impl`` round whose cut commits: the masks
    the taken arm rebuilt are the reference's of the committed state."""
    vc = cluster(c)
    vc.crash([3, 17, 29])
    rng = np.random.default_rng(300 + c)
    # a blocked top cohort that silences nobody's cut: nobody is deaf to the crashed alone
    rx_block = np.zeros((c, SLOTS), dtype=bool)
    rx_block[c - 1, rng.choice(SLOTS - MEMBERS, 4, replace=False) + MEMBERS] = True
    cfg, state = vc.cfg, vc.state
    faults = vc.faults._replace(rx_block=jnp.asarray(rx_block))
    step = jax.jit(vcm.engine_step_carried_impl, static_argnums=(0,))
    masks = reference_edge_masks(cfg, state, faults)
    for _ in range(12):
        epoch = int(state.config_epoch)
        state, events, masks = step(cfg, state, faults, masks)
        if bool(events.decided):
            assert int(state.config_epoch) == epoch + 1  # the taken arm ran
            assert_same(masks, reference_edge_masks(cfg, state, faults), f"carried c={c}: the cut's round")
            assert not np.asarray(state.alive)[[3, 17, 29]].any()
            return
        # the other arm hands back what it was given
        assert_same(masks, reference_edge_masks(cfg, state, faults), f"carried c={c}: a quiet round")
    pytest.fail("no cut committed in 12 rounds")


@pytest.fixture(scope="module")
def compiled():
    yield
    jax.clear_caches()  # tier-1 runs near the process's limit of memory maps


@pytest.mark.parametrize("c", COHORTS)
@pytest.mark.parametrize("how", [_eager, _jit, _vmap, _carried_step], ids=lambda f: f.__name__.strip("_"))
def test_both_outputs_equal_the_two_gather_body_bit_for_bit(compiled, how, c):
    how(c)


def test_the_build_traces_one_gather():
    """One look-up an edge: the traced build holds ONE gather, whatever the
    cohort count puts in the table."""
    for c in (8, 64):
        vc = cluster(c)
        text = str(jax.make_jaxpr(lambda s, f: vcm._edge_masks(vc.cfg, s, f))(vc.state, vc.faults))
        assert text.count(" gather[") == 1, (c, text.count(" gather["))
