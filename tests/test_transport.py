"""Lossy D2D transport: frame codec, loss determinism, error feedback
(DESIGN.md §11).

Four layers are pinned here:

* **Host byte codec** — fragment/reassemble round-trips for arbitrary
  payload sizes and MTUs (hypothesis property tests, degrading to the
  shim's single-example mode without it), exact header accounting, CRC
  rejection of corrupted frames, and a golden on-air frame dump under
  ``tests/golden/`` so codec changes can't silently break wire
  compatibility.
* **Static layout consistency** — the in-jit per-leaf frame arithmetic
  (``LossyTransport.leaf_framing``) must agree exactly with what the
  host codec produces when fragmenting the serialized buffers.
* **Fault injection** (marked ``faults``) — deterministic loss patterns
  from ``tests/faults.py`` produce identical delivered-frame sets and
  trajectories across the Host/Scan/Shard engines, run to run and
  engine to engine; ``erasure=0`` stays bitwise identical to the
  no-transport teleport path on every engine.
* **Error feedback** (marked ``faults``) — under 10–30% frame erasure
  the CHOCO control sequence keeps cdbfl within tolerance of the
  lossless trajectory; switching feedback off measurably degrades it
  (the mechanism, not just the happy path).

Run ``pytest -m "not faults"`` to deselect the engine-heavy injection
suite locally; tier-1 CI runs everything.
"""
import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.config import FedConfig, TopologyConfig, TransportConfig
from repro.core import (LossyTransport, build_topology, make_round_fn,
                        resolve_transport)
from repro.core.compression import parse_pipeline
from repro.core.gossip import make_mixer
from repro.core.topology import build_schedule
from repro.core.transport import (HEADER_BYTES, frame_sizes, fragment,
                                  lora_toa_s, model_from_config, num_frames,
                                  parse_frame, reassemble, serialize_payload)
import faults

NDEV = len(jax.devices())
needs2 = pytest.mark.skipif(NDEV < 2, reason="needs >=2 devices "
                            "(XLA_FLAGS=--xla_force_host_platform_"
                            "device_count=8)")
needs4 = pytest.mark.skipif(NDEV < 4, reason="needs >=4 devices")

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


def _payload_bytes(nbytes: int, seed: int) -> bytes:
    rng = np.random.default_rng(seed * 7919 + nbytes)
    return rng.integers(0, 256, nbytes, np.uint8).tobytes()


# --------------------------------------------------------------------------
# host byte codec: properties
# --------------------------------------------------------------------------

@given(nbytes=st.integers(min_value=0, max_value=3000),
       mtu=st.integers(min_value=9, max_value=300),
       seed=st.integers(min_value=0, max_value=10 ** 6))
def test_fragment_roundtrip(nbytes, mtu, seed):
    data = _payload_bytes(nbytes, seed)
    frames = fragment(data, mtu)
    sizes = frame_sizes(nbytes, mtu)
    # exact header accounting: every frame is its payload plus 8 bytes,
    # frames never exceed the MTU, and the layout table matches reality
    assert [len(f) for f in frames] == sizes.tolist()
    assert all(len(f) <= mtu for f in frames)
    assert sum(sizes) == nbytes + HEADER_BYTES * len(frames)
    assert num_frames(nbytes, mtu) == len(frames)
    # reassembly is order-independent
    shuffled = list(frames)
    np.random.default_rng(seed).shuffle(shuffled)
    out, received = reassemble(shuffled, nbytes, mtu)
    assert out == data
    assert received.all()


@given(nbytes=st.integers(min_value=1, max_value=3000),
       mtu=st.integers(min_value=9, max_value=300),
       seed=st.integers(min_value=0, max_value=10 ** 6))
def test_reassemble_with_dropped_subset(nbytes, mtu, seed):
    data = _payload_bytes(nbytes, seed)
    frames = fragment(data, mtu)
    n = len(frames)
    rng = np.random.default_rng(seed + 1)
    drop = set(rng.choice(n, size=rng.integers(0, n + 1), replace=False)
               .tolist())
    kept = [None if i in drop else f for i, f in enumerate(frames)]
    out, received = reassemble(kept, nbytes, mtu)
    assert len(out) == nbytes
    assert received.tolist() == [i not in drop for i in range(n)]
    cap = mtu - HEADER_BYTES
    for i in range(n):
        lo, hi = i * cap, min((i + 1) * cap, nbytes)
        want = data[lo:hi] if i not in drop else b"\x00" * (hi - lo)
        assert out[lo:hi] == want


@given(nbytes=st.integers(min_value=1, max_value=800),
       mtu=st.integers(min_value=9, max_value=120),
       seed=st.integers(min_value=0, max_value=10 ** 6))
def test_crc_rejects_corruption(nbytes, mtu, seed):
    data = _payload_bytes(nbytes, seed)
    frames = fragment(data, mtu)
    rng = np.random.default_rng(seed + 2)
    victim = int(rng.integers(0, len(frames)))
    frame = bytearray(frames[victim])
    pos = int(rng.integers(0, len(frame)))
    frame[pos] ^= 1 + int(rng.integers(0, 255))
    corrupted = list(frames)
    corrupted[victim] = bytes(frame)
    out, received = reassemble(corrupted, nbytes, mtu)
    # a flipped bit anywhere (header or payload) kills exactly that frame
    assert not received[victim]
    assert received.sum() >= len(frames) - 1
    cap = mtu - HEADER_BYTES
    for i in range(len(frames)):
        if received[i]:
            lo, hi = i * cap, min((i + 1) * cap, nbytes)
            assert out[lo:hi] == data[lo:hi]


def test_parse_frame_rejects_truncation_and_bad_length():
    (frame,) = fragment(b"hello world", 64)
    assert parse_frame(frame) == (0, b"hello world")
    assert parse_frame(frame[:5]) is None            # truncated header
    assert parse_frame(frame[:-3]) is None           # truncated payload
    assert parse_frame(frame + b"xx") is None        # over-long payload


def test_zero_byte_payload_is_one_header_only_frame():
    frames = fragment(b"", 64)
    assert len(frames) == 1 and len(frames[0]) == HEADER_BYTES
    out, received = reassemble(frames, 0, 64)
    assert out == b"" and received.all()
    assert frame_sizes(0, 64).tolist() == [HEADER_BYTES]


def test_mtu_must_fit_header():
    with pytest.raises(ValueError):
        frame_sizes(100, HEADER_BYTES)
    with pytest.raises(ValueError):
        fragment(b"x", HEADER_BYTES)


def test_seq_is_uint16_bounded():
    with pytest.raises(ValueError):
        fragment(b"\x00" * 70000, 9)                 # 70000 one-byte frames


def test_unknown_loss_model_rejected():
    with pytest.raises(ValueError):
        model_from_config(TransportConfig(loss_model="laplace"))


# --------------------------------------------------------------------------
# serialized payload vs the in-jit static layout
# --------------------------------------------------------------------------

def _demo_payload(pipeline="block_topk|sign", ratio=0.25, block=8):
    tree = {"a": jnp.asarray(np.linspace(-1.0, 1.0, 48, dtype=np.float32)
                             .reshape(4, 12)),
            "b": jnp.asarray(np.linspace(0.5, -0.5, 11, dtype=np.float32))}
    pipe = parse_pipeline(pipeline, ratio=ratio, block_size=block)
    return pipe, tree, pipe.encode(tree, jax.random.PRNGKey(0))


def test_serialize_payload_matches_measured_bytes():
    _, _, payload = _demo_payload()
    data = serialize_payload(payload)
    assert len(data) == payload.measured_bytes()
    assert sum(payload.per_leaf_bytes()) == len(data)


@pytest.mark.parametrize("mtu", [16, 48, 256])
def test_static_framing_matches_host_codec(mtu):
    """The jit-side frame arithmetic equals fragmenting the real bytes."""
    _, _, payload = _demo_payload()
    transport = faults.make_transport(mtu=mtu)
    data = serialize_payload(payload)
    offset = 0
    for nbytes in payload.per_leaf_bytes():
        leaf_bytes = data[offset:offset + nbytes]
        offset += nbytes
        frames = fragment(leaf_bytes, mtu)
        fr = transport.leaf_framing(nbytes, (len(leaf_bytes),))
        assert fr.n_frames == len(frames)
        assert fr.frame_bytes.tolist() == [len(f) for f in frames]
        # every record lands in a frame that exists
        assert fr.record_frame.max() < fr.n_frames


# --------------------------------------------------------------------------
# golden wire format: on-air bytes are frozen
# --------------------------------------------------------------------------

GOLDEN_MTU = 64


def _golden_frames():
    _, _, payload = _demo_payload()
    data = serialize_payload(payload)
    frames = fragment(data, GOLDEN_MTU)
    manifest = {
        "mtu": GOLDEN_MTU,
        "header_bytes": HEADER_BYTES,
        "payload_bytes": len(data),
        "per_leaf_bytes": [int(b) for b in payload.per_leaf_bytes()],
        "n_frames": len(frames),
        "frame_sizes": [len(f) for f in frames],
        "frame_crc32": [zlib.crc32(f) & 0xFFFFFFFF for f in frames],
    }
    return b"".join(frames), manifest


def test_golden_wire_format():
    """Byte-for-byte stability of the header layout + packed encoding.

    Regenerate deliberately with REPRO_REGEN_GOLDEN=1 after an
    *intentional* wire-format change — the dump is the on-air contract.
    """
    blob, manifest = _golden_frames()
    bin_path = os.path.join(GOLDEN_DIR, "transport_frames.bin")
    man_path = os.path.join(GOLDEN_DIR, "transport_frames.json")
    if os.environ.get("REPRO_REGEN_GOLDEN"):
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        with open(bin_path, "wb") as f:
            f.write(blob)
        with open(man_path, "w") as f:
            json.dump(manifest, f, indent=1)
    assert os.path.exists(bin_path), \
        "golden frame dump missing; run with REPRO_REGEN_GOLDEN=1"
    with open(man_path) as f:
        want_manifest = json.load(f)
    assert manifest == want_manifest
    with open(bin_path, "rb") as f:
        want = f.read()
    assert blob == want, "on-air frame bytes drifted from tests/golden/"
    # and the committed dump still reassembles to the committed payload
    sizes = want_manifest["frame_sizes"]
    frames, off = [], 0
    for s in sizes:
        frames.append(want[off:off + s])
        off += s
    out, received = reassemble(frames, want_manifest["payload_bytes"],
                               GOLDEN_MTU)
    assert received.all()
    _, _, payload = _demo_payload()
    assert out == serialize_payload(payload)


# --------------------------------------------------------------------------
# loss models: PRNG purity and pattern shapes
# --------------------------------------------------------------------------

KEY = jax.random.PRNGKey(42)


def test_fixed_mask_drops_exactly_the_listed_frames():
    model = faults.fixed_drop(0, 3)
    keep = np.asarray(model.keep(KEY, 5, 0))
    assert keep.tolist() == [0.0, 1.0, 1.0, 0.0, 1.0]
    assert np.asarray(model.keep(KEY, 2, 1)).tolist() == [0.0, 1.0]


def test_asymmetric_rates_are_per_node_exact():
    model = faults.asymmetric([0.0, 1.0, 0.0, 0.0])
    assert np.asarray(model.keep(KEY, 6, 0)).tolist() == [1.0] * 6
    assert np.asarray(model.keep(KEY, 6, 1)).tolist() == [0.0] * 6


def test_dead_node_wrapper_zeroes_listed_senders():
    model = faults.dead_nodes(2, base=faults.fixed_drop(1))
    assert np.asarray(model.keep(KEY, 3, 2)).tolist() == [0.0] * 3
    assert np.asarray(model.keep(KEY, 3, 0)).tolist() == [1.0, 0.0, 1.0]


def test_gilbert_elliott_is_bursty_and_deterministic():
    model = faults.bursty(p_enter=0.1, p_exit=0.4)
    keep = np.asarray(model.keep(KEY, 400, 0))
    again = np.asarray(model.keep(KEY, 400, 0))
    np.testing.assert_array_equal(keep, again)
    # stationary bad fraction is p_enter/(p_enter+p_exit) = 0.2
    assert 0.08 < 1.0 - keep.mean() < 0.35
    # loss comes in episodes: some run of >=2 consecutive erasures exists
    runs = "".join("x" if k == 0 else "." for k in keep)
    assert "xx" in runs
    # a different key realizes a different episode pattern
    other = np.asarray(model.keep(jax.random.PRNGKey(43), 400, 0))
    assert not np.array_equal(keep, other)


def test_bernoulli_keep_depends_on_key_not_call_order():
    model = faults.make_transport(erasure=0.5).model
    a = np.asarray(model.keep(KEY, 64, 0))
    b = np.asarray(model.keep(KEY, 64, 0))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, np.asarray(
        model.keep(jax.random.PRNGKey(7), 64, 0)))


# --------------------------------------------------------------------------
# SNR-parameterized link outage (the gossip dropout seam)
# --------------------------------------------------------------------------

def _ring_schedule(k=8):
    cfg = TopologyConfig(graph="ring")
    return build_schedule(build_topology(cfg, k).omega)


def test_snr_outage_matrix_is_valid_and_edge_symmetric():
    sched = _ring_schedule()
    t = faults.make_transport(num_nodes=8, snr_db=8.0, snr_spread_db=4.0,
                              snr_threshold_db=0.0)
    p = t.outage_probs(sched)
    assert p.shape == sched.perms.shape
    assert np.all((p >= 0.0) & (p <= 1.0))
    # min-of-endpoints SNR makes the outage symmetric per edge — required
    # for the realized mixer to stay doubly stochastic
    for m in range(p.shape[0]):
        np.testing.assert_allclose(p[m], p[m][sched.perms[m]])
    # fixed points (unmatched rows) never "fail"
    fixed = sched.perms == np.arange(sched.k)[None, :]
    assert np.all(p[fixed] == 0.0)


def test_snr_outage_monotone_in_snr():
    sched = _ring_schedule()
    lo = faults.make_transport(num_nodes=8, snr_db=3.0).outage_probs(sched)
    hi = faults.make_transport(num_nodes=8, snr_db=15.0).outage_probs(sched)
    assert np.all(hi <= lo)
    assert hi.max() < lo.max()


def test_snr_draws_are_seed_deterministic():
    a = faults.make_transport(num_nodes=8, snr_db=5.0, snr_spread_db=6.0,
                              seed=3).snr_per_node()
    b = faults.make_transport(num_nodes=8, snr_db=5.0, snr_spread_db=6.0,
                              seed=3).snr_per_node()
    c = faults.make_transport(num_nodes=8, snr_db=5.0, snr_spread_db=6.0,
                              seed=4).snr_per_node()
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_dead_links_silence_the_edge_in_the_mixer():
    """All ring edges dead -> the time-varying mixer is the identity."""
    cfg = TopologyConfig(graph="ring")
    topo = build_topology(cfg, 8)
    sched = build_schedule(topo.omega)
    edges = sorted({tuple(sorted((k, int(sched.perms[m, k]))))
                    for m in range(sched.num_perms) for k in range(8)
                    if k != int(sched.perms[m, k])})
    mixer = make_mixer(topo.omega, config=cfg,
                       link_probs=faults.dead_links(edges))
    tree = {"w": jnp.asarray(np.arange(24.0, dtype=np.float32)
                             .reshape(8, 3))}
    out = mixer(tree, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    # one live edge pair: the mean is preserved (doubly stochastic masks)
    mixer2 = make_mixer(topo.omega, config=cfg,
                        link_probs=faults.dead_links(edges[1:]))
    out2 = mixer2(tree, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(out2["w"]).mean(0),
                               np.asarray(tree["w"]).mean(0), atol=1e-5)
    assert not np.array_equal(np.asarray(out2["w"]), np.asarray(tree["w"]))


def test_link_probs_shape_mismatch_raises():
    cfg = TopologyConfig(graph="ring")
    topo = build_topology(cfg, 8)
    with pytest.raises(ValueError):
        make_mixer(topo.omega, config=cfg,
                   link_probs=lambda sched: np.zeros((1, 3)))


# --------------------------------------------------------------------------
# resolve/guard plumbing
# --------------------------------------------------------------------------

def test_resolve_transport_explicit_override_wins():
    fed = FedConfig(num_nodes=4, transport=TransportConfig(erasure=0.5))
    override = faults.make_transport(erasure=0.0)
    assert resolve_transport(fed, override) is override
    built = resolve_transport(fed)
    assert isinstance(built, LossyTransport) and built.lossy
    assert resolve_transport(FedConfig(num_nodes=4)) is None


def test_explicit_mixer_plus_link_outage_raises():
    fed = FedConfig(num_nodes=faults.K, topology="ring", algorithm="cdbfl",
                    compressor="topk", compress_ratio=0.5)
    topo = build_topology(faults.resolve_topology(fed), faults.K)
    from repro.core import make_compressor
    comp = make_compressor(fed)
    t = faults.make_transport(snr_db=3.0)
    with pytest.raises(ValueError, match="mixer"):
        make_round_fn("cdbfl", faults.linear_loss, fed, topo.omega, comp,
                      mixer=lambda tree, key=None: tree, transport=t)


# --------------------------------------------------------------------------
# fault injection: engine equivalence + byte accounting
# --------------------------------------------------------------------------

def _tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _tree_close(a, b, atol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=atol, rtol=0)


@pytest.mark.faults
@pytest.mark.parametrize("engine", ["host", "scan"])
@pytest.mark.parametrize("algorithm", ["cdbfl", "cffl"])
def test_erasure_zero_is_bitwise_teleport(engine, algorithm):
    """A configured-but-lossless transport must not perturb a single bit
    of the trajectory — the acceptance criterion for the retrofit."""
    plain = faults.run_world(engine, algorithm, transport=None)
    framed = faults.run_world(engine, algorithm,
                              transport=TransportConfig(mtu=32, erasure=0.0))
    _tree_equal(plain.state.params, framed.state.params)
    _tree_equal(plain.state.v, framed.state.v)
    np.testing.assert_array_equal(plain.losses, framed.losses)
    # ... while the accounting now includes the frame headers
    assert framed.offered[-1] > framed.wire[-1] > 0
    assert framed.delivered == framed.offered
    assert framed.airtime[-1] > 0 and framed.energy[-1] > 0
    assert plain.offered[-1] == 0.0


@needs2
@pytest.mark.faults
def test_erasure_zero_is_bitwise_teleport_shard():
    plain = faults.run_world("shard", "cdbfl", transport=None, s=2)
    framed = faults.run_world(
        "shard", "cdbfl", transport=TransportConfig(mtu=32, erasure=0.0),
        s=2)
    _tree_equal(plain.state.params, framed.state.params)
    _tree_equal(plain.state.v, framed.state.v)
    assert framed.delivered == framed.offered
    assert framed.offered[-1] > framed.wire[-1] > 0


@pytest.mark.faults
def test_lossy_run_is_seed_deterministic():
    spec = TransportConfig(mtu=16, erasure=0.3)
    a = faults.run_world("scan", "cdbfl", transport=spec)
    b = faults.run_world("scan", "cdbfl", transport=spec)
    assert a.delivered == b.delivered
    _tree_equal(a.state.params, b.state.params)
    np.testing.assert_array_equal(a.losses, b.losses)
    # a different round seed realizes a different delivered-frame set
    c = faults.run_world("scan", "cdbfl", transport=spec, seed=2)
    assert a.delivered != c.delivered


@pytest.mark.faults
@pytest.mark.parametrize("model_kind", ["bernoulli", "burst", "asym"])
def test_host_and_scan_agree_under_loss(model_kind):
    """Same seed + same loss spec -> identical delivered-frame sets and
    matching trajectories on both single-device engines (host jits each
    round standalone: 1-ulp fma slack on params, bytes exact)."""
    model = {
        "bernoulli": None,                       # config path, rate 0.25
        "burst": faults.bursty(p_enter=0.2, p_exit=0.5),
        "asym": faults.asymmetric([0.0, 0.6, 0.1, 0.9]),
    }[model_kind]
    t = (TransportConfig(mtu=16, erasure=0.25) if model is None
         else faults.make_transport(model=model, mtu=16))
    h = faults.run_world("host", "cdbfl", transport=t)
    s = faults.run_world("scan", "cdbfl", transport=t)
    assert h.delivered == s.delivered
    assert h.offered == s.offered
    _tree_close(h.state.params, s.state.params, atol=5e-7)


@needs2
@pytest.mark.faults
def test_scan_and_shard_agree_bitwise_under_loss():
    """The loss masks key off the *global* node id, so the sharded run
    realizes the identical erasure pattern: bit-for-bit state."""
    spec = TransportConfig(mtu=16, erasure=0.25)
    s_c = faults.run_world("scan", "cdbfl", transport=spec)
    s_s = faults.run_world("shard", "cdbfl", transport=spec, s=2)
    _tree_equal(s_c.state.params, s_s.state.params)
    _tree_equal(s_c.state.v, s_s.state.v)
    assert s_c.delivered == s_s.delivered
    assert s_c.offered == s_s.offered


@needs4
@pytest.mark.faults
def test_shard_count_invariance_under_loss():
    spec = TransportConfig(mtu=16, erasure=0.25)
    a = faults.run_world("shard", "cdbfl", transport=spec, s=2)
    b = faults.run_world("shard", "cdbfl", transport=spec, s=4)
    _tree_equal(a.state.params, b.state.params)
    assert a.delivered == b.delivered


@pytest.mark.faults
def test_dead_node_byte_accounting_is_exact():
    """One dead transmitter out of K=4: the delivered mean is exactly
    3/4 of offered, every round (bytes are integer-exact in f32)."""
    t = faults.make_transport(model=faults.dead_nodes(1), mtu=32)
    run = faults.run_world("scan", "cdbfl", transport=t, rounds=4)
    assert run.offered == [26.0] * 4          # 18B topk payload + header
    assert run.delivered == [26.0 * 3 / 4] * 4


@pytest.mark.faults
def test_fixed_drop_byte_accounting_is_exact():
    """mtu=16 -> the 18-byte payload rides 3 frames (16, 16, 10 bytes);
    dropping frame 1 on every node loses exactly 16 bytes each."""
    t = faults.make_transport(model=faults.fixed_drop(1), mtu=16)
    run = faults.run_world("scan", "cdbfl", transport=t, rounds=4)
    assert run.offered == [42.0] * 4
    assert run.delivered == [26.0] * 4


@pytest.mark.faults
def test_dsgld_dense_accounting():
    """The uncompressed baseline reports framed dense bytes (offered ==
    delivered: no codec, no feedback — the robustness gap CD-BFL
    closes), and its trajectory ignores the transport entirely."""
    t = TransportConfig(mtu=32)
    run = faults.run_world("scan", "dsgld", transport=t, rounds=4)
    plain = faults.run_world("scan", "dsgld", transport=None, rounds=4)
    assert run.wire == [24.0] * 4             # 6 f32 dense
    assert run.offered == [32.0] * 4          # + one 8-byte header
    assert run.delivered == run.offered
    _tree_equal(plain.state.params, run.state.params)


@pytest.mark.faults
def test_snr_outage_run_is_finite_and_deterministic():
    t = TransportConfig(mtu=32, erasure=0.1, snr_db=4.0, snr_spread_db=6.0,
                        snr_threshold_db=0.0)
    a = faults.run_world("scan", "cdbfl", transport=t)
    b = faults.run_world("scan", "cdbfl", transport=t)
    _tree_equal(a.state.params, b.state.params)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(a.state.params))
    assert a.delivered == b.delivered


# --------------------------------------------------------------------------
# error feedback: the contraction that keeps compression convergent
# --------------------------------------------------------------------------

def _consensus(run):
    return np.asarray(run.state.params["w"]).mean(axis=0)


@pytest.mark.faults
@pytest.mark.parametrize("erasure", [0.1, 0.3])
def test_error_feedback_contracts_under_loss(erasure):
    """With residual memory on, cdbfl under 10-30% frame erasure stays
    within tolerance of the lossless posterior-mean trajectory; with it
    off, the sender's control sequence absorbs mass the neighbors never
    saw and the same run measurably degrades."""
    rounds, chunk = 24, 8
    lossless = faults.run_world("scan", "cdbfl", transport=None,
                                rounds=rounds, chunk=chunk)
    fb = faults.run_world(
        "scan", "cdbfl", rounds=rounds, chunk=chunk,
        transport=TransportConfig(mtu=16, erasure=erasure,
                                  error_feedback=True))
    nofb = faults.run_world(
        "scan", "cdbfl", rounds=rounds, chunk=chunk,
        transport=TransportConfig(mtu=16, erasure=erasure,
                                  error_feedback=False))
    ref = _consensus(lossless)
    scale = np.linalg.norm(ref)
    d_fb = np.linalg.norm(_consensus(fb) - ref) / scale
    d_nofb = np.linalg.norm(_consensus(nofb) - ref) / scale
    # stated tolerance: feedback holds the consensus within 20% of the
    # lossless trajectory at these erasure rates on this problem
    assert d_fb < 0.20, f"feedback run drifted {d_fb:.3f} from lossless"
    assert d_nofb > 2.0 * d_fb, \
        f"feedback off should degrade: {d_nofb:.3f} vs {d_fb:.3f}"
    # and the training loss tells the same story
    assert fb.losses[-1] < nofb.losses[-1]


@pytest.mark.faults
def test_error_feedback_keeps_losses_finite_under_heavy_burst():
    t = faults.make_transport(model=faults.bursty(p_enter=0.3, p_exit=0.3),
                              mtu=16)
    run = faults.run_world("scan", "cdbfl", transport=t, rounds=12, chunk=4)
    assert np.isfinite(run.losses).all()
    assert 0 < run.delivered[-1] <= run.offered[-1]


# --------------------------------------------------------------------------
# LoRa time-on-air: the budget currency (DESIGN.md §12)
# --------------------------------------------------------------------------

def test_lora_toa_reference_values():
    """SX127x datasheet arithmetic, pinned: SF7/125kHz/CR4-5 and SF12
    (which crosses the 16 ms symbol threshold -> low-data-rate optimize)."""
    np.testing.assert_allclose(float(lora_toa_s(25)), 0.061696, rtol=1e-9)
    np.testing.assert_allclose(float(lora_toa_s(25, sf=12)), 1.482752,
                               rtol=1e-9)
    # vectorized over frame sizes, monotone in payload and SF
    toa = lora_toa_s(np.array([10, 25, 100]))
    assert toa.shape == (3,) and np.all(np.diff(toa) > 0)
    assert float(lora_toa_s(25, sf=9)) > float(lora_toa_s(25, sf=7))
    # doubling bandwidth exactly halves airtime
    np.testing.assert_allclose(float(lora_toa_s(25, bw_hz=250_000.0)),
                               0.5 * float(lora_toa_s(25)), rtol=1e-12)


def test_lora_toa_validation():
    for sf in (5, 13):
        with pytest.raises(ValueError):
            lora_toa_s(25, sf=sf)
    for cr in (0, 5):
        with pytest.raises(ValueError):
            lora_toa_s(25, coding_rate=cr)


def test_arq_transport_properties():
    # no period -> unbounded budget; arq alone doesn't make the wire lossy
    t = faults.make_transport(arq=True, max_retries=3, erasure=0.0)
    assert t.max_attempts == 4 and not t.lossy
    assert t.airtime_budget_s == float("inf") and not t.budgeted
    # a finite duty-cycled budget can abandon frames even at erasure=0
    tb = faults.make_transport(arq=True, toa=True, duty_cycle=0.01,
                               round_period_s=10.0)
    assert tb.budgeted and tb.lossy
    np.testing.assert_allclose(tb.airtime_budget_s, 0.1)
    # arq off clamps to single-shot regardless of max_retries
    assert faults.make_transport(arq=False, max_retries=5).max_attempts == 1


# --------------------------------------------------------------------------
# ARQ: selective-repeat retransmission under a round-time budget (§12)
# --------------------------------------------------------------------------

@pytest.mark.faults
@pytest.mark.parametrize("engine", ["host", "scan"])
def test_arq_lossless_unbudgeted_is_bitwise_teleport(engine):
    """ARQ on + erasure=0 + budget=inf must not perturb a single bit —
    the acceptance criterion for the reliability retrofit."""
    plain = faults.run_world(engine, "cdbfl", transport=None)
    arq = faults.run_world(engine, "cdbfl",
                           transport=TransportConfig(mtu=32, erasure=0.0,
                                                     arq=True, max_retries=2))
    _tree_equal(plain.state.params, arq.state.params)
    _tree_equal(plain.state.v, arq.state.v)
    np.testing.assert_array_equal(plain.losses, arq.losses)
    assert arq.retransmits == [0.0] * len(arq.retransmits)
    assert arq.abandoned == [0.0] * len(arq.abandoned)


@pytest.mark.faults
def test_arq_recovers_delivered_bytes_under_erasure():
    """30% frame erasure, max_retries=2: delivered bytes strictly
    increase over the single-shot run (the ISSUE acceptance gate), at
    the cost of real retransmit airtime."""
    base = TransportConfig(mtu=32, erasure=0.3)
    arq = TransportConfig(mtu=32, erasure=0.3, arq=True, max_retries=2)
    r0 = faults.run_world("scan", "cdbfl", transport=base)
    r2 = faults.run_world("scan", "cdbfl", transport=arq)
    assert sum(r2.delivered) > sum(r0.delivered)
    assert sum(r2.retransmits) > 0 and sum(r0.retransmits) == 0
    assert sum(r2.offered) > sum(r0.offered)       # retries hit the air
    assert sum(r2.airtime) > sum(r0.airtime)
    # and the retry schedule is seed-deterministic
    again = faults.run_world("scan", "cdbfl", transport=arq)
    assert r2.retransmits == again.retransmits
    assert r2.delivered == again.delivered
    _tree_equal(r2.state.params, again.state.params)


@pytest.mark.faults
def test_arq_host_and_scan_agree():
    spec = TransportConfig(mtu=16, erasure=0.3, arq=True, max_retries=2)
    h = faults.run_world("host", "cdbfl", transport=spec)
    s = faults.run_world("scan", "cdbfl", transport=spec)
    assert h.delivered == s.delivered
    assert h.retransmits == s.retransmits
    assert h.abandoned == s.abandoned
    _tree_close(h.state.params, s.state.params, atol=5e-7)


@needs2
@pytest.mark.faults
def test_arq_scan_and_shard_agree_bitwise():
    """Per-attempt keep masks key off (global node id, leaf, attempt), so
    the sharded run realizes the identical retransmit sets: bit-for-bit
    state and identical retransmit histories."""
    spec = TransportConfig(mtu=16, erasure=0.3, arq=True, max_retries=2)
    s_c = faults.run_world("scan", "cdbfl", transport=spec)
    s_s = faults.run_world("shard", "cdbfl", transport=spec, s=2)
    _tree_equal(s_c.state.params, s_s.state.params)
    _tree_equal(s_c.state.v, s_s.state.v)
    assert s_c.delivered == s_s.delivered
    assert s_c.retransmits == s_s.retransmits
    assert s_c.abandoned == s_s.abandoned


@pytest.mark.faults
def test_drop_first_attempt_forces_retransmit_path():
    """Deterministic ARQ exercise: every frame dies on attempt 0 and
    arrives on attempt 1. Without ARQ nothing is ever delivered; with
    one retry everything is, at exactly 2x the offered traffic."""
    model = faults.drop_first_attempts(1)
    dead = faults.make_transport(model=model, mtu=32)
    run0 = faults.run_world("scan", "cdbfl", transport=dead, rounds=4)
    assert run0.delivered == [0.0] * 4
    arq = faults.make_transport(model=model, mtu=32, arq=True, max_retries=1)
    run1 = faults.run_world("scan", "cdbfl", transport=arq, rounds=4)
    assert run1.delivered == [26.0] * 4       # 18B topk payload + header
    assert run1.offered == [52.0] * 4         # every frame sent twice
    assert run1.retransmits == [1.0] * 4      # one frame per node per round
    assert run1.abandoned == [0.0] * 4


@pytest.mark.faults
def test_budget_exhaustion_abandons_to_residual():
    """A starved duty-cycle budget abandons every frame: nothing is
    delivered, the abandoned mass is accounted, and CHOCO error feedback
    keeps the run finite (mass rides the residual, DESIGN.md §11)."""
    t = faults.make_transport(mtu=32, erasure=0.0, arq=True, max_retries=2,
                              toa=True, duty_cycle=0.01, round_period_s=0.001)
    run = faults.run_world("scan", "cdbfl", transport=t, rounds=6)
    assert run.delivered == [0.0] * 6
    assert run.abandoned == [26.0] * 6
    assert run.airtime == [0.0] * 6           # nothing cleared the budget
    assert np.isfinite(run.losses).all()
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(run.state.params))


@pytest.mark.faults
def test_partial_budget_delivers_prefix_and_abandons_rest():
    """A budget that fits only part of the payload transmits a frame
    prefix and abandons the tail — delivered + abandoned == payload."""
    toa_frame = float(lora_toa_s(16))
    # three frames of the mtu=16 layout are (16, 16, 10) bytes; budget
    # covers roughly the first two
    t = faults.make_transport(mtu=16, erasure=0.0, arq=True, max_retries=0,
                              toa=True, duty_cycle=1.0,
                              round_period_s=2.1 * toa_frame)
    run = faults.run_world("scan", "cdbfl", transport=t, rounds=4)
    assert run.offered == [32.0] * 4          # frames 0+1 fit the budget
    assert run.delivered == [32.0] * 4
    assert run.abandoned == [10.0] * 4        # the 10-byte tail never flies
    assert all(a > 0 for a in run.airtime)


@pytest.mark.faults
def test_toa_airtime_accounting_matches_formula():
    """With toa=on the per-round airtime equals the SX127x ToA of the
    actual frame layout, not the flat PHY-rate estimate."""
    t = faults.make_transport(mtu=32, erasure=0.0, toa=True)
    run = faults.run_world("scan", "cdbfl", transport=t, rounds=4)
    want = float(lora_toa_s(26))              # one 26-byte frame per node
    np.testing.assert_allclose(run.airtime, [want] * 4, rtol=1e-6)


@pytest.mark.faults
def test_dsgld_dense_accounting_reports_toa():
    """The frequentist baseline's static accounting carries the same ToA
    columns, keeping the robustness-gap comparison fair under the new
    accounting."""
    t = faults.make_transport(mtu=32, erasure=0.0, toa=True)
    run = faults.run_world("scan", "dsgld", transport=t, rounds=4)
    assert run.wire == [24.0] * 4             # 6 f32 dense
    assert run.offered == [32.0] * 4
    want = float(lora_toa_s(32))              # one 32-byte dense frame
    np.testing.assert_allclose(run.airtime, [want] * 4, rtol=1e-6)
    assert run.retransmits == [0.0] * 4 and run.abandoned == [0.0] * 4


# --------------------------------------------------------------------------
# calibration survives ARQ-recovered loss: the ISSUE 7 acceptance run
# --------------------------------------------------------------------------

@pytest.mark.faults
def test_arq_holds_calibration_under_30pct_erasure(radar_world):
    """30% frame erasure with max_retries=2 on the radar task: delivered
    bytes strictly increase over single-shot, and the final ECE stays
    within 0.02 of the lossless run (ISSUE 7 acceptance)."""
    from repro.train import FedTrainer
    cfg, model, shards, test = radar_world

    def _fed(transport=None):
        return FedConfig(num_nodes=5, local_steps=4, eta=3e-3, zeta=0.3,
                         rounds=50, burn_in=30, compressor="block_topk",
                         compress_ratio=0.05, topology="full",
                         algorithm="cdbfl", transport=transport)

    lossless = FedTrainer(model, _fed(), shards, minibatch=8)
    res_clean = lossless.run(rounds=50, eval_batch=test)
    arq = FedTrainer(model, _fed(TransportConfig(mtu=64, erasure=0.3,
                                                 arq=True, max_retries=2)),
                     shards, minibatch=8)
    res_arq = arq.run(rounds=50, eval_batch=test)
    single = FedTrainer(model, _fed(TransportConfig(mtu=64, erasure=0.3)),
                        shards, minibatch=8)
    res_single = single.run(rounds=50, eval_batch=test)
    # retransmissions recover real bytes the single-shot run loses
    assert res_arq.delivered_bytes_per_round > \
        res_single.delivered_bytes_per_round
    assert res_arq.retransmits_per_round > 0
    # and calibration survives the recovered channel
    assert np.isfinite(res_arq.ece) and np.isfinite(res_clean.ece)
    assert abs(res_arq.ece - res_clean.ece) < 0.02, \
        f"ECE drift {res_arq.ece:.4f} vs lossless {res_clean.ece:.4f}"
    assert res_arq.accuracy > 0.4
