"""SPMD shard execution: ppermute gossip + ShardRoundEngine (DESIGN.md §4).

Two layers of equivalence are pinned here:

* mixer level — ``shard_mix`` (the ``make_shard_mixer`` lowering executed
  inside ``shard_map``) must be *bitwise* identical per node to
  ``make_mixer``'s single-device execution (``schedule_mix`` roll /
  Laplacian paths, dense all-gather oracle) on every topology family,
  including time-varying schedules with link dropout and gossip-pair
  sampling: the shard path moves data with ``lax.ppermute``, but performs
  the same elementwise arithmetic in the same order.
* engine level — :class:`ShardRoundEngine` must reproduce the
  :class:`HostRoundEngine` trajectory for cdbfl/dsgld/cffl on a ≥4-device
  CPU mesh: per-node state (params, control sequences, posterior bank) is
  bitwise identical to the scan engine and within 1 ulp of the host loop
  (the host loop jits each round standalone, and LLVM's fma contraction
  differs between a standalone jit and a scan body — a pre-existing
  property visible between scan and host engines, not introduced by
  sharding).

These tests need forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the tier1-spmd CI
job); on a single-device run they skip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FedConfig, TopologyConfig
from repro.core import (ShardContext, build_topology, init_fed_state,
                        make_compressor, make_round_fn, make_shard_mixer,
                        plan_shard_mix, resolve_topology)
from repro.core.gossip import make_mixer, plan_mixer
from repro.core.posterior import DeviceSampleBank
from repro.core.topology import GRAPHS, build_schedule
from repro.data.partition import DeviceShards
from repro.train.engine import make_engine

NDEV = len(jax.devices())
needs2 = pytest.mark.skipif(NDEV < 2, reason="needs >=2 devices "
                            "(XLA_FLAGS=--xla_force_host_platform_"
                            "device_count=8)")
needs4 = pytest.mark.skipif(NDEV < 4, reason="needs >=4 devices")

K = 8
KEY = jax.random.PRNGKey(0)


def _mesh(s):
    from repro.launch.mesh import make_fed_mesh
    return make_fed_mesh(s)


def _tree(k=K):
    return {"a": jax.random.normal(jax.random.PRNGKey(7), (k, 5, 3)),
            "b": jax.random.normal(jax.random.PRNGKey(8), (k, 11))}


def _run_shard_mixer(omega, cfg, s, tree, key=None):
    """Execute the shard mixer inside shard_map on an S-shard mesh."""
    from jax.sharding import PartitionSpec as P
    ctx = ShardContext("fed", s)
    mixer, stats = make_shard_mixer(omega, ctx, config=cfg)
    specs = jax.tree.map(lambda _: P("fed"), tree)

    def local(t, k):
        return mixer(t, k)

    fn = jax.shard_map(local, mesh=_mesh(s), in_specs=(specs, P()),
                       out_specs=specs, check_vma=False)
    return jax.jit(fn)(tree, key if key is not None
                       else jax.random.PRNGKey(1)), stats


def _topo_cfg(graph, **kw):
    return TopologyConfig(graph=graph, degree=4, edge_prob=0.4, radius=0.5,
                          seed=3, **kw)


def _host_mix(omega, cfg, tree, key):
    """Jitted host mixer: the bitwise comparison must hold jit-to-jit
    (eager CPU execution skips the fma contraction jit applies)."""
    return jax.jit(lambda t, k: make_mixer(omega, config=cfg)(t, k))(tree, key)


# --------------------------------------------------------------------------
# shard_mix vs schedule_mix vs dense_mix, every topology family
# --------------------------------------------------------------------------

@needs2
@pytest.mark.parametrize("graph", GRAPHS)
@pytest.mark.parametrize("s", [2, 4])
def test_shard_mix_matches_host_mixer(graph, s):
    if s > NDEV:
        pytest.skip(f"needs {s} devices")
    cfg = _topo_cfg(graph)
    topo = build_topology(cfg, K)
    tree = _tree()
    host = _host_mix(topo.omega, cfg, tree, jax.random.PRNGKey(1))
    got, _ = _run_shard_mixer(topo.omega, cfg, s, tree)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@needs2
@pytest.mark.parametrize("graph", GRAPHS)
def test_shard_mix_matches_dense_oracle(graph):
    """End-to-end exactness: the ppermute lowering equals the Ω einsum."""
    from repro.core.gossip import dense_mix
    cfg = _topo_cfg(graph)
    topo = build_topology(cfg, K)
    tree = _tree()
    want = dense_mix(topo.omega, tree)
    got, _ = _run_shard_mixer(topo.omega, cfg, 2, tree)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@needs2
@pytest.mark.parametrize("graph", ["ring", "torus", "k_regular",
                                   "erdos_renyi", "geometric", "full"])
@pytest.mark.parametrize("tv", [dict(link_failure_prob=0.35),
                                dict(gossip_pairs=1),
                                dict(link_failure_prob=0.2, gossip_pairs=2)])
def test_shard_mix_time_varying_matches_host(graph, tv):
    """Per-round dropout/pair masks are drawn from the replicated key the
    same way on every shard, so even the time-varying realization is
    bitwise identical to the host mixer."""
    cfg = _topo_cfg(graph, **tv)
    topo = build_topology(cfg, K)
    tree = _tree()
    for r in range(3):                   # several round keys
        key = jax.random.fold_in(KEY, r)
        host = _host_mix(topo.omega, cfg, tree, key)
        got, _ = _run_shard_mixer(topo.omega, cfg, 2, tree, key=key)
        for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_shard_mix_reconstructs_permutations():
    """Pure-numpy check (no mesh): the per-delta ppermute lists reassemble
    every matching permutation exactly."""
    for graph in GRAPHS:
        topo = build_topology(_topo_cfg(graph), K)
        mode, schedule = plan_mixer(topo.omega, _topo_cfg(graph))
        if schedule is None:
            schedule = build_schedule(topo.omega)
        if schedule.num_perms == 0:
            continue
        for s in (2, 4, 8):
            plan = plan_shard_mix(schedule, s)
            lk = plan.local_k
            for m, ex in enumerate(plan.matchings):
                perm = schedule.perms[m]
                got = np.zeros(K, np.int32)
                for r in range(s):
                    # start from the intra-shard gather…
                    rows = r * lk + ex.local_src[r]
                    for (d, send_idx, recv_slot, recv_mask) in ex.deltas:
                        src_shard = (r + d) % s
                        buf = src_shard * lk + send_idx[src_shard]
                        rows = np.where(recv_mask[r], buf[recv_slot[r]], rows)
                    got[r * lk:(r + 1) * lk] = rows
                np.testing.assert_array_equal(got, perm, err_msg=graph)


def test_shard_mix_stats_ring():
    """Ring on 4 shards of 2: each node exchanges with 2 neighbors; one of
    them sits across a shard boundary on average (2 boundary rows per
    shard of 2 nodes)."""
    topo = build_topology(_topo_cfg("ring"), K)
    ctx = ShardContext("fed", 4)
    _, stats = make_shard_mixer(topo.omega, ctx, config=_topo_cfg("ring"))
    assert stats.mode == "roll"
    assert stats.cross_rows == pytest.approx(1.0)
    assert stats.intra_rows == pytest.approx(1.0)


# --------------------------------------------------------------------------
# ShardRoundEngine vs HostRoundEngine / ScanRoundEngine trajectories
# --------------------------------------------------------------------------

L, M, DIM = 3, 5, 6


def linear_loss(params, batch, key):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), ()


def _shards(sizes=(17, 20, 20, 13, 15, 19, 11, 20)):
    rng = np.random.default_rng(0)
    out = []
    for n in sizes:
        x = rng.normal(size=(n, DIM)).astype(np.float32)
        w = np.arange(1.0, DIM + 1.0, dtype=np.float32) / DIM
        out.append({"x": x, "y": (x @ w).astype(np.float32)})
    return out


def _world(algorithm, topology="ring"):
    fed = FedConfig(num_nodes=K, local_steps=L, eta=5e-3, zeta=0.3,
                    burn_in=4, compressor="topk", compress_ratio=0.5,
                    topology=topology, algorithm=algorithm)
    topo = build_topology(resolve_topology(fed), K)
    comp = make_compressor(fed)
    dshards = DeviceShards.from_shards(_shards())
    bank_cfg = DeviceSampleBank(burn_in=4, capacity=5, thin=2)
    params0 = {"w": jnp.zeros((DIM,))}
    return fed, topo, comp, dshards, bank_cfg, params0


def _run(engine_name, algorithm, rounds=12, s=4, chunk=4, topology="ring"):
    fed, topo, comp, dshards, bank_cfg, params0 = _world(algorithm, topology)
    bayes = algorithm in ("cdbfl", "dsgld")
    kwargs = {}
    shard_ctx = None
    if engine_name == "shard":
        kwargs = dict(mesh=_mesh(s))
        shard_ctx = ShardContext("fed", s)
    rf = make_round_fn(algorithm, linear_loss, fed, topo.omega, comp,
                       data_scale=10.0, shard_ctx=shard_ctx)
    eng = make_engine(engine_name, rf, dshards, L, M,
                      bank=bank_cfg if bayes else None, chunk=chunk, **kwargs)
    state = init_fed_state(params0, fed, key=KEY)
    if not bayes:
        bank0 = None
    elif engine_name == "host":
        bank0 = eng.make_bank()
    else:
        bank0 = bank_cfg.init(state.params)
    state, key, bank, losses, cons = eng.run(state, jax.random.PRNGKey(1),
                                             bank0, rounds)
    return state, bank, losses, cons, bank_cfg, eng


@needs4
@pytest.mark.parametrize("algorithm", ["cdbfl", "dsgld", "cffl"])
def test_shard_engine_matches_host_trajectory(algorithm):
    rounds = 12
    s_h, b_h, loss_h, cons_h, cfg, _ = _run("host", algorithm, rounds)
    s_s, b_s, loss_s, cons_s, _, eng = _run("shard", algorithm, rounds, s=4)
    # per-node state: exact up to the host loop's standalone-jit fma (1 ulp)
    for a, b in zip(jax.tree.leaves(s_h.params), jax.tree.leaves(s_s.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-7, rtol=0)
    assert int(s_h.round) == int(s_s.round) == rounds
    np.testing.assert_allclose(loss_h, loss_s, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(cons_h, cons_s, atol=1e-4, rtol=1e-4)
    if algorithm in ("cdbfl", "dsgld"):
        host_samples = b_h.samples
        shard_samples = cfg.samples_list(b_s)
        assert len(host_samples) == len(shard_samples) > 0
        for hs, ss in zip(host_samples, shard_samples):
            for a, b in zip(jax.tree.leaves(hs), jax.tree.leaves(ss)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=5e-7, rtol=0)
    # explicit ppermute gossip reports nonzero cross-shard traffic
    assert eng.last_cross_history[-1] > 0


@needs4
@pytest.mark.parametrize("algorithm", ["cdbfl", "dsgld", "cffl"])
def test_shard_engine_bitwise_matches_scan(algorithm):
    """Same fusion regime (scan-fused super-rounds): bit-for-bit state."""
    s_c, b_c, _, _, cfg, _ = _run("scan", algorithm)
    s_s, b_s, _, _, _, _ = _run("shard", algorithm, s=4)
    for a, b in zip(jax.tree.leaves(s_c.params), jax.tree.leaves(s_s.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_c.v), jax.tree.leaves(s_s.v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if algorithm in ("cdbfl", "dsgld"):
        for hs, ss in zip(cfg.samples_list(b_c), cfg.samples_list(b_s)):
            for a, b in zip(jax.tree.leaves(hs), jax.tree.leaves(ss)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@needs2
def test_shard_engine_shard_count_invariance():
    """2 vs 4 vs 8 shards: the trajectory must not depend on the mesh."""
    base = _run("shard", "cdbfl", s=2)
    for s in (4, 8):
        if s > NDEV:
            continue
        got = _run("shard", "cdbfl", s=s)
        for a, b in zip(jax.tree.leaves(base[0].params),
                        jax.tree.leaves(got[0].params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(base[2], got[2], atol=1e-6)


@needs4
def test_shard_engine_dense_graph():
    """Full graph rides the all-gather oracle inside shard_map."""
    s_h, _, loss_h, _, _, _ = _run("host", "cffl", topology="full")
    s_s, _, loss_s, _, _, eng = _run("shard", "cffl", s=4, topology="full")
    for a, b in zip(jax.tree.leaves(s_h.params), jax.tree.leaves(s_s.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-7, rtol=0)
    np.testing.assert_allclose(loss_h, loss_s, atol=1e-5, rtol=1e-5)
    # dense all-gather: every node's row visits the other S-1 shards
    assert eng.last_cross_history[-1] > 0


@needs2
def test_gspmd_auto_scan_matches_host():
    """GSPMD-auto (--mesh with the scan engine): sharded placement only,
    compiler-inserted collectives, same trajectory."""
    from repro.launch.sharding import place_fed_state
    fed, topo, comp, dshards, bank_cfg, params0 = _world("cdbfl")
    rf = make_round_fn("cdbfl", linear_loss, fed, topo.omega, comp,
                       data_scale=10.0)
    mesh = _mesh(2)
    eng = make_engine("scan", rf, dshards.with_sharding(mesh, "fed"),
                      L, M, bank=bank_cfg, chunk=4)
    state = place_fed_state(init_fed_state(params0, fed, key=KEY),
                            mesh, "fed")
    bank0 = bank_cfg.init(state.params)
    s_a, _, _, loss_a, _ = eng.run(state, jax.random.PRNGKey(1), bank0, 12)
    s_h, _, loss_h, _, _, _ = _run("host", "cdbfl")
    for a, b in zip(jax.tree.leaves(s_h.params), jax.tree.leaves(s_a.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-7, rtol=0)
    np.testing.assert_allclose(loss_h, loss_a, atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# satellite: the dryrun import guard
# --------------------------------------------------------------------------

def test_dryrun_import_does_not_clobber_xla_flags():
    """Importing dryrun helpers must not mutate XLA_FLAGS (the forced
    512-device count is an entry-point decision, not an import effect)."""
    import os
    before = os.environ.get("XLA_FLAGS")
    import repro.launch.dryrun  # noqa: F401
    assert os.environ.get("XLA_FLAGS") == before


def test_force_host_device_count_noop_after_init():
    """Once a backend exists the helper refuses to rewrite XLA_FLAGS."""
    import os
    import warnings
    from repro.launch.xla_flags import force_host_device_count
    jax.devices()                        # ensure initialized
    before = os.environ.get("XLA_FLAGS")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert force_host_device_count(NDEV + 1) is False
    assert os.environ.get("XLA_FLAGS") == before
