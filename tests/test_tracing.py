"""Where the time goes: the round's named scopes, the engines' host spans
and the serving engine's slot counters.

* every phase of the CD-BFL round reaches the compiled chunk program's HLO
  as a ``jax.named_scope`` in each op's ``op_name`` (through scan, vmap and
  grad), with the fused codec and with the jnp codec;
* under ``jax.profiler`` one ``engine.run`` and three ``ClassifyEngine``
  steps write the ``engine.*`` and ``serve.*`` host spans, nested as the
  engines document, ``serve.step`` carrying its step index;
* the serving engine counts occupied slots and stamps each response with
  the steps it waited.
"""
import glob
import re

import jax
import numpy as np
import pytest

from repro.config import FedConfig, ServeConfig, get_arch
from repro.core import (build_topology, init_fed_state, make_compressor,
                        make_round_fn, resolve_topology)
from repro.core.posterior import DeviceSampleBank
from repro.data.partition import DeviceShards, partition_iid
from repro.data.radar import make_dataset
from repro.models import get_model
from repro.serve import ClassifyEngine, ServeRequest
from repro.train.engine import make_engine

HW = (16, 16)
K, L, B = 3, 2, 2
ROUND_SCOPES = ("sample_batches", "local_step", "encode", "decode", "mix",
                "langevin_noise", "round_metrics", "bank_admit")
ENGINE_SPANS = ("engine.dispatch", "engine.sync_metrics", "engine.histories")
SERVE_PHASES = ("serve.admit", "serve.predict", "serve.fetch", "serve.retire")


def _lenet():
    return get_model(get_arch("lenet-radar").reduced.replace(input_hw=HW))


def _training(fused: bool):
    """A K=3 ring of tiny LeNets on the scan engine, with a bank."""
    model = _lenet()
    fed = FedConfig(num_nodes=K, local_steps=L, eta=1e-4, zeta=0.3,
                    topology="ring", compressor="block_topk",
                    compress_ratio=0.05, block_size=64,
                    fused_compress=fused, algorithm="cdbfl")
    topo = build_topology(resolve_topology(fed), K)
    round_fn = make_round_fn("cdbfl", model.loss, fed, topo.omega,
                             make_compressor(fed), data_scale=1.0)
    shards = DeviceShards.from_shards(
        partition_iid(make_dataset(4 * K, hw=HW, seed=3), K))
    bank = DeviceSampleBank(burn_in=0, capacity=2, thin=1)
    engine = make_engine("scan", round_fn, shards, L, B, bank=bank, chunk=2)
    state = init_fed_state(model.init(jax.random.PRNGKey(0)), fed,
                           key=jax.random.PRNGKey(1))
    return engine, state, bank.init(state.params)


def _scopes_in(hlo_text: str) -> set:
    """Every path component of every op_name, transform wrappers such as
    ``vmap(transpose(jvp(local_step)))`` stripped."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        for part in path.split("/"):
            while re.fullmatch(r"\w+\((.*)\)", part):
                part = re.fullmatch(r"\w+\((.*)\)", part).group(1)
            out.add(part)
    return out


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "jnp"])
def test_chunk_program_carries_every_round_scope(fused):
    engine, state, bank = _training(fused)
    text = engine.lower_chunk(state, jax.random.PRNGKey(2), bank,
                              rounds=2).compile().as_text()
    missing = set(ROUND_SCOPES) - _scopes_in(text)
    assert not missing, missing


def _host_events(trace_dir):
    """(name, start_ns, end_ns, stats) of every host event in the trace."""
    files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    assert files
    pd = jax.profiler.ProfileData.from_file(files[-1])
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_and_serve_spans_nest_in_a_trace(tmp_path):
    engine, state, bank = _training(fused=False)
    model = _lenet()
    stacked = jax.tree.map(lambda x: x[None], model.init(
        jax.random.PRNGKey(4)))
    serve = ClassifyEngine(lambda p, b: model.logits(p, b),
                           ServeConfig(slots=2), input_shape=HW + (1,),
                           stacked=stacked)
    frames = make_dataset(5, hw=HW, seed=6)["x"]
    # compile both engines' programs before the trace (the carry is donated)
    state, key, bank, _, _ = engine.run(state, jax.random.PRNGKey(2), bank,
                                        2, log_every=2)
    for x in frames:
        serve.submit(ServeRequest(x=x))
    serve.drain()
    for x in frames:
        serve.submit(ServeRequest(x=x))
    first_step = serve.steps

    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run(state, key, bank, 2, log_every=2)
        for _ in range(3):
            serve.step()
    finally:
        jax.profiler.stop_trace()

    events = _host_events(tmp_path)
    spans = {n: [e for e in events if e[0] == n]
             for n in ENGINE_SPANS + SERVE_PHASES + ("serve.step",)}
    # one chunk: dispatch, then the metrics sync, then the histories
    assert [len(spans[n]) for n in ENGINE_SPANS] == [1, 1, 1]
    dispatch, sync, hists = (spans[n][0] for n in ENGINE_SPANS)
    assert dispatch[2] <= sync[1] and sync[2] <= hists[1]
    # three steps, each holding its four phases in order
    steps = sorted(spans["serve.step"], key=lambda e: e[1])
    assert [s[3]["step"] for s in steps] == [first_step, first_step + 1,
                                             first_step + 2]
    assert [s[3]["occupied"] for s in steps] == [2, 2, 1]
    for s in steps:
        phases = [[e for e in spans[n] if _inside(e, s)] for n in SERVE_PHASES]
        assert [len(p) for p in phases] == [1, 1, 1, 1]
        starts = [p[0][1] for p in phases]
        assert starts == sorted(starts)


def test_slot_counters_and_queue_stamps():
    model = _lenet()
    stacked = jax.tree.map(lambda x: x[None], model.init(
        jax.random.PRNGKey(4)))
    serve = ClassifyEngine(lambda p, b: model.logits(p, b),
                           ServeConfig(slots=8), input_shape=HW + (1,),
                           stacked=stacked)
    frames = make_dataset(10, hw=HW, seed=6)["x"]
    rids = [serve.submit(ServeRequest(x=x)) for x in frames]
    out = sorted(serve.drain(), key=lambda r: r.request_id)
    assert [r.request_id for r in out] == rids
    assert serve.steps == 2 and serve.slot_steps == 10
    assert [r.queued_steps for r in out] == [0] * 8 + [1] * 2
    assert [r.admit_step for r in out] == [0] * 8 + [1] * 2
    assert [r.done_step for r in out] == [r.admit_step for r in out]
    for r in out:
        assert 0.0 <= r.queue_s <= r.latency_s
    st = serve.stats()
    assert st["steps"] == 2 and st["slot_steps"] == 10 and st["served"] == 10
    assert st["slot_occupancy"] == 10 / 16 and st["queued_share"] == 0.2
    assert st["queue_ms_mean"] == pytest.approx(
        1e3 * np.mean([r.queue_s for r in out]))
    lat_ms = np.array([r.latency_s for r in out]) * 1e3
    # percentiles come from buckets 2**(1/16) wide: within 5% of exact
    for q in (50, 99):
        assert st[f"p{q}_ms"] == pytest.approx(np.percentile(lat_ms, q),
                                               rel=0.05)
