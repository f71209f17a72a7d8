"""Run CD-BFL training and BMA serving once on a TPU, at the paper's width.

    python chip_smoke.py                 # one chip: the phases below
    python chip_smoke.py --four-chips    # four chips: sharded fleet vs one chip

One process drives every phase through the entry points a user calls
(``repro.launch.train.main``, ``repro.launch.serve.main``), on the paper's
model: ``lenet-radar`` at 256x63 input, p = 2,598,846, with K=10 nodes on a
ring, L=4 local steps and 1% block top-k, for 6 rounds.

1. ``train_scan``: the scan engine, with a posterior bank (capacity 4,
   burn-in 2) snapshotted to a checkpoint directory. Losses are finite,
   and the consensus error, which rises from 0 because every node starts
   from the same params, rises more slowly in the second half.
2. ``train_host``: the same run on ``HostRoundEngine``, the reference
   oracle; the node params agree with phase 1.
3. ``train_fused``: the same run with ``--fused-compress`` (the Pallas
   delta-pack and unpack kernels); every leaf's decoded delta equals the
   jnp ``BlockTopKCodec`` decode of the same residual.
4. ``serve``: 16 classify requests through the serving path on the bank
   from phase 1, with zero recompiles after warmup; the probabilities are
   finite, sum to 1 and match ``ScanEvalEngine`` on the same bank.

``--four-chips`` runs only K=40 nodes sharded over four chips
(``ShardRoundEngine``, ppermute gossip) against ``ScanRoundEngine`` on one
chip: the node axis spans all four devices, the per-round losses and
consensus agree, and each node's params match their counterpart (see
``NODE_GAP``). Each phase prints its wall time with the compile time
inside it; these are set-up times, not performance.

Exit codes: 0 with the contract line below, only on a TPU with every check
passed; 2 when JAX finds no TPU; 3 after a ``--cpu-rehearsal`` whose checks
all passed (reduced widths, on the CPU: never a result); anything else is
a failed check. The last line of standard output on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

NODES, ROUNDS, LOCAL_STEPS, RATIO = 10, 6, 4, 0.01
FOUR_CHIP_NODES = 40
REQUESTS = 16
# scan-vs-host agreement of the node params after ROUNDS rounds: the
# engines run the same ops in different programs, so XLA may fuse and round
# differently. Across chips the same bound is only reported, leaf by leaf.
PARAM_RTOL, PARAM_ATOL = 1e-4, 1e-5
PROB_ATOL = 1e-6
# Across chips the bound is on each node as a whole. The local steps of 10
# nodes per chip and of 40 on one chip are programs of different batch
# size, so their float32 sums may differ in the last bit; where that
# reaches a near-tie at a block's k-th magnitude, block top-k sends another
# entry, and that one entry moves by a residual. A node placed, mixed or
# keyed wrongly lands as far from its counterpart as from another node.
TRAJ_RTOL = 1e-4
NODE_GAP = 0.05


def _args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the K=40 four-chip sharded phase and its "
                         "one-chip comparison")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run every check at reduced widths on the CPU, "
                         "then exit 3 without a result line")
    return ap.parse_args()


class _Compiles:
    """XLA compile seconds and persistent-cache hits, from JAX's
    monitoring events (tracing is left out: nested traces overlap)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


class _Phase:
    def __init__(self, name, compiles):
        self.name, self.compiles = name, compiles

    def __enter__(self):
        print(f"== phase {self.name}", flush=True)
        self.t0 = time.perf_counter()
        self.c0 = self.compiles.snapshot()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        c, h, m = (b - a for a, b in zip(self.c0, self.compiles.snapshot()))
        print(f"[{self.name}] set-up time (not performance): wall {wall:.2f}s"
              f", of it XLA compiling {c:.2f}s; persistent cache hits {h}, "
              f"misses {m}", flush=True)
        return False


def _check(ok, what):
    print(f"  {'PASS' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise SystemExit(f"check failed: {what}")


def _max_abs_diff(a, b):
    import jax
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _params_agree(a, b, what):
    import jax
    import numpy as np
    close = all(np.allclose(np.asarray(x), np.asarray(y), rtol=PARAM_RTOL,
                            atol=PARAM_ATOL)
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    _check(close, f"{what}: node params allclose (rtol={PARAM_RTOL:g}, "
                  f"atol={PARAM_ATOL:g}); max |diff| "
                  f"{_max_abs_diff(a, b):.3e}")


def _fleets_agree(a, b, what):
    """Sharded vs one-chip run of the same fleet: per-round losses and
    consensus agree, and each node's params lie closer to its own
    counterpart than ``NODE_GAP`` of the way to any other node."""
    import jax
    import numpy as np

    for name, x, y in (("losses", a.losses, b.losses),
                       ("consensus", a.consensus, b.consensus)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        rel = float(np.max(np.abs(x - y) / np.abs(y)))
        _check(rel <= TRAJ_RTOL, f"{what}: per-round {name} agree (max "
                                 f"relative diff {rel:.3e} <= {TRAJ_RTOL:g})")
    k = jax.tree.leaves(b.state.params)[0].shape[0]
    self_sq = np.zeros(k)
    gram = np.zeros((k, k))
    for (path, x), y in zip(
            jax.tree_util.tree_flatten_with_path(a.state.params)[0],
            jax.tree.leaves(b.state.params)):
        x = np.asarray(x, np.float64).reshape(k, -1)
        y = np.asarray(y, np.float64).reshape(k, -1)
        d = np.abs(x - y)
        off = int(np.sum(d > PARAM_ATOL + PARAM_RTOL * np.abs(y)))
        print(f"  {jax.tree_util.keystr(path)}: max |diff| {d.max():.3e}; "
              f"{off} of {d.size} entries outside allclose(rtol="
              f"{PARAM_RTOL:g}, atol={PARAM_ATOL:g})")
        self_sq += np.sum(d * d, axis=1)
        gram += y @ y.T
    sq = np.diag(gram)
    pair = np.sqrt(np.maximum(sq[:, None] + sq[None, :] - 2 * gram, 0.0))
    np.fill_diagonal(pair, np.inf)
    ratio = np.sqrt(self_sq) / pair.min(axis=1)
    worst = int(np.argmax(ratio))
    _check(bool(np.all(ratio <= NODE_GAP)),
           f"{what}: every node's params match its counterpart (worst node "
           f"{worst}: distance {np.sqrt(self_sq[worst]):.3e}, "
           f"{ratio[worst]:.3e} of the {pair[worst].min():.3e} to its "
           f"nearest other node; bound {NODE_GAP:g})")


def _train_argv(trim, nodes=NODES, extra=()):
    argv = ["--arch", "lenet-radar", "--nodes", str(nodes),
            "--topology", "ring", "--local-steps", str(LOCAL_STEPS),
            "--compressor", "block_topk", "--ratio", str(RATIO),
            "--rounds", str(ROUNDS), "--log-every", "3"]
    return argv + (["--trim"] if trim else []) + list(extra)


def _check_training(run, what):
    import numpy as np
    losses, cons = np.asarray(run.losses), np.asarray(run.consensus)
    print(f"  {what}: losses {np.round(losses, 4).tolist()}")
    print(f"  {what}: consensus {[f'{c:.3e}' for c in cons]}")
    _check(len(losses) == ROUNDS and np.all(np.isfinite(losses)),
           f"{what}: {ROUNDS} finite losses")
    # every node starts from the same params, so the consensus error rises
    # from 0 as each node draws its own Langevin noise; the gossip pulls the
    # nodes back together, so the rise slows: rounds 4-6 add less than 1-3
    half = ROUNDS // 2
    first, second = cons[half - 1], cons[-1] - cons[half - 1]
    _check(bool(np.all(np.isfinite(cons))) and 0 < second < first,
           f"{what}: consensus error finite, and its growth slows "
           f"(+{first:.4e} in rounds 1-{half}, +{second:.4e} in rounds "
           f"{half + 1}-{ROUNDS})")


def _check_fused_decode(run):
    """Every leaf's decoded delta from the fused Pallas encode equals the
    jnp block top-k decode of the same residual (θ - v), for every node."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.compression import BlockTopKCodec, FusedCodec
    from repro.kernels.ref import block_topk_ref

    comp = run.compressor
    _check(isinstance(comp, FusedCodec) and comp.fused,
           "the fused run encoded through FusedCodec")
    codec = BlockTopKCodec(ratio=RATIO)
    bs = codec.block_size
    k = int(np.ceil(RATIO * bs))
    key = jax.random.PRNGKey(11)

    def reference(x):
        # jnp BlockTopKCodec for multi-block leaves; a leaf of at most one
        # block is packed block-wise by the kernels (no global-top-k
        # fallback), so its reference is the same top_k on the padded block
        if x.size > bs:
            return codec.decode(*codec.encode(x, key))
        row = jnp.zeros((1, bs), x.dtype).at[0, :x.size].set(x.reshape(-1))
        return block_topk_ref(row, k)[0, :x.size].reshape(x.shape)

    def fused(theta, v):
        return comp.decode(comp.encode_pair(theta, v, key))

    def ref(theta, v):
        return jax.tree.map(lambda t, c: reference(t - c.astype(t.dtype)),
                            theta, v)

    state = run.state
    got = jax.jit(jax.vmap(fused))(state.params, state.v)
    want = jax.jit(jax.vmap(ref))(state.params, state.v)
    total = 0
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        nnz = int(np.count_nonzero(w))
        total += nnz
        _check(np.array_equal(g, w),
               f"leaf {jax.tree_util.keystr(path)} {g.shape}: fused Pallas "
               f"decode == jnp decode ({nnz} nonzeros over {g.shape[0]} "
               f"nodes)")
    _check(total > 0, f"the residuals were not all zero ({total} nonzeros)")


def _check_serving(srv):
    import numpy as np
    from repro.config import get_arch
    from repro.eval.engine import ScanEvalEngine
    from repro.models import get_model

    probs = np.stack([r.probs for r in srv.responses])
    _check(len(srv.responses) == REQUESTS,
           f"{REQUESTS} requests answered")
    _check(srv.recompiles == 0, "zero recompiles after warmup")
    _check(bool(np.all(np.isfinite(probs))), "serve probabilities finite")
    sums = probs.sum(axis=1)
    _check(bool(np.allclose(sums, 1.0, atol=1e-5)),
           f"serve probabilities sum to 1 (max |sum-1| "
           f"{float(np.max(np.abs(sums - 1))):.2e})")
    model = get_model(get_arch("lenet-radar").config)
    data = {"x": np.stack([r.x for r in srv.requests]),
            "y": np.zeros(len(srv.requests), np.int32)}
    _, eval_probs = ScanEvalEngine(lambda p, b: model.logits(p, b),
                                   batch_size=8).evaluate(
        srv.bank, data, node_axis=srv.node_axis, return_probs=True)
    diff = float(np.max(np.abs(probs - eval_probs)))
    _check(bool(np.allclose(probs, eval_probs, rtol=0, atol=PROB_ATOL)),
           f"serve probabilities match ScanEvalEngine on the same bank "
           f"(atol={PROB_ATOL:g}; max |diff| {diff:.3e}, bitwise "
           f"{bool(np.array_equal(probs, eval_probs))})")


def run_one_chip(trim, compiles, workdir):
    from repro.launch import serve, train

    ckpt = os.path.join(workdir, "ckpt")
    bank = ["--bank-capacity", "4", "--burn-in", "2"]
    with _Phase("train_scan", compiles):
        scan = train.main(_train_argv(trim, extra=bank + [
            "--engine", "scan", "--ckpt-dir", ckpt]))
        _check_training(scan, "scan")
        _check(scan.bank is not None, "posterior bank snapshotted to "
                                      f"{os.path.basename(ckpt)}/")
    with _Phase("train_host", compiles):
        host = train.main(_train_argv(trim, extra=bank + ["--engine",
                                                          "host"]))
        _check_training(host, "host")
        _params_agree(scan.state.params, host.state.params,
                      "scan engine vs HostRoundEngine")
        del host
    with _Phase("train_fused", compiles):
        fused = train.main(_train_argv(trim, extra=[
            "--engine", "scan", "--fused-compress"]))
        _check_training(fused, "fused")
        _check_fused_decode(fused)
        del fused
    with _Phase("serve", compiles):
        srv = serve.main(["--arch", "lenet-radar", "--ckpt-dir", ckpt,
                          "--requests", str(REQUESTS), "--smoke"]
                         + (["--trim"] if trim else []))
        _check_serving(srv)


def run_four_chips(trim, compiles):
    import jax
    from repro.launch import train

    devices = jax.devices()
    _check(len(devices) >= 4, f"four devices visible ({len(devices)})")
    with _Phase("train_shard_4", compiles):
        shard = train.main(_train_argv(trim, nodes=FOUR_CHIP_NODES, extra=[
            "--mesh", "4", "--engine", "shard"]))
        _check_training(shard, "shard")
        for path, x in jax.tree_util.tree_flatten_with_path(
                shard.state.params)[0]:
            held = {d.id for d in x.sharding.device_set}
            _check(len(held) == 4 and x.sharding.shard_shape(x.shape)[0]
                   == FOUR_CHIP_NODES // 4,
                   f"params{jax.tree_util.keystr(path)}: node axis split "
                   f"over devices {sorted(held)}")
    with _Phase("train_scan_1", compiles):
        one = train.main(_train_argv(trim, nodes=FOUR_CHIP_NODES,
                                     extra=["--engine", "scan"]))
        _check_training(one, "scan")
        _check(all(len(x.sharding.device_set) == 1
                   for x in jax.tree.leaves(one.state.params)),
               "the one-chip comparison ran on one device")
        _fleets_agree(shard, one, "ShardRoundEngine (4 chips) vs "
                                  "ScanRoundEngine (1 chip)")


def main():
    args = _args()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"no TPU: JAX found {dev.platform} devices only; nothing "
              f"was run", file=sys.stderr)
        sys.exit(2)

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    trim = dev.platform != "tpu"
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f"; jax {jax.__version__}; compile cache {cache}"
          + ("; CPU rehearsal at reduced widths" if trim else ""),
          flush=True)
    compiles = _Compiles()
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(trim, compiles)
    else:
        with tempfile.TemporaryDirectory(prefix=".smoke_", dir=ROOT) as wd:
            run_one_chip(trim, compiles, wd)
    print(f"all phases passed; set-up time {time.perf_counter() - t0:.2f}s"
          f" in total, {compiles.seconds:.2f}s of it XLA compiling",
          flush=True)
    if trim:
        print("CPU rehearsal passed; no chip, so no result", file=sys.stderr)
        sys.exit(3)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
