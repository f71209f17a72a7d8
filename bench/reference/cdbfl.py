"""Plain CD-BFL (Barbieri et al. 2024, Algorithm 1), node by node.

One round, for every node k of a graph with mixing weights Omega:

1. L local steps on f_k = NLL(minibatch) + (1 / 2K) * |theta|^2, each
   ``theta -= eta * grad f_k`` (Eq. 5);
2. the residual ``theta_L - v_k`` is compressed by block top-k: every block of
   ``block`` entries of a leaf (the last one zero-padded) keeps its
   ``ceil(ratio * block)`` largest magnitudes, the lower index first among
   equals (Eq. 6);
3. ``v_k += delta_k`` and ``vbar_k += sum_j Omega_kj delta_j`` (Eqs. 7-8);
4. ``theta_k = theta_L + zeta * (vbar_k - v_k) + sqrt(2 eta T) * xi_k``
   (Eq. 9).

The random streams are the algorithm's, stated as part of what a seed
means: per round ``key, kround = split(key)``; node k's minibatch indices
are ``randint(fold_in(fold_in(kround, 7), k), (L, B), 0, n_k)``; with
``kql, knoise = split(kround)``, node k's noise for the i-th leaf (in
sorted-key order) is ``normal(split(fold_in(knoise, k), leaves)[i])``.

The loop over nodes is plain Python; nothing is batched across nodes.
``dtype`` is the precision of every array and operation (float32 at
``highest`` for the reference, bfloat16 for the control).
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

DATA_SALT = 7


def ring_weights(k: int) -> np.ndarray:
    """Metropolis-Hastings weights of a ring: 1 / (1 + max degree)."""
    w = np.zeros((k, k))
    if k == 1:
        return np.ones((1, 1))
    for i in range(k):
        for j in {(i - 1) % k, (i + 1) % k} - {i}:
            w[i, j] = 1.0 / (1.0 + 2.0 if k > 2 else 2.0)
        w[i, i] = 1.0 - w[i].sum()
    return w


def block_topk(x, ratio: float, block: int):
    keep = max(1, math.ceil(ratio * block))
    n = x.size
    nb = max(1, -(-n // block))
    flat = jnp.zeros((nb * block,), x.dtype).at[:n].set(x.reshape(-1))
    flat = flat.reshape(nb, block)
    _, idx = jax.lax.top_k(jnp.abs(flat), keep)
    rows = jnp.arange(nb)[:, None]
    out = jnp.zeros_like(flat).at[rows, idx].set(
        jnp.take_along_axis(flat, idx, axis=1))
    return out.reshape(-1)[:n].reshape(x.shape)


class CDBFL:
    """``nll(params, batch, dtype)`` is the model's mean NLL; ``traffic``
    gives nodes, local_steps, batch, eta, zeta, temperature, ratio, block."""

    def __init__(self, nll: Callable, traffic: dict, dtype=jnp.float32):
        self.t = traffic
        self.dtype = jnp.dtype(dtype)
        # arrays are stored in dtype; float8 has no arithmetic of its own,
        # so a float8 control stores bfloat16 and rounds matmul operands
        self.store = (jnp.dtype(jnp.bfloat16)
                      if jnp.dtype(dtype).itemsize == 1 else self.dtype)
        self.k = int(traffic["nodes"])
        self.omega = ring_weights(self.k)
        eta, k = float(traffic["eta"]), self.k

        def f(theta, batch):
            prior = sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(theta))
            return nll(theta, batch, self.dtype) + 0.5 * (1.0 / k) * prior

        def local(theta, batches):
            losses = []
            for step in range(int(traffic["local_steps"])):
                b = jax.tree.map(lambda a: a[step], batches)
                loss, g = jax.value_and_grad(f)(theta, b)
                theta = jax.tree.map(
                    lambda a, d: (a - jnp.asarray(eta, a.dtype) * d
                                  ).astype(self.store), theta, g)
                losses.append(loss.astype(jnp.float32))
            return theta, jnp.stack(losses)

        ratio, block = float(traffic["ratio"]), int(traffic["block"])
        scale = jnp.sqrt(jnp.float32(
            2.0 * eta * float(traffic.get("temperature", 1.0))))

        def node_step(theta, v, batches):
            """Local steps and the compressed residual of one node."""
            theta_l, losses = local(theta, batches)
            delta = jax.tree.map(lambda a, b: block_topk(a - b, ratio, block),
                                 theta_l, v)
            return theta_l, losses, delta

        def mix(weights, deltas):
            """sum_j Omega_kj delta_j over the node's neighbours."""
            return jax.tree.map(
                lambda *ds: sum(jnp.asarray(w, ds[0].dtype) * d
                                for w, d in zip(weights, ds)), *deltas)

        def finish(theta_l, v, vbar, delta, mixed, knoise, node, noise_sum):
            """Control sequences, consensus correction and noise."""
            v = jax.tree.map(lambda a, d: a + d, v, delta)
            vbar = jax.tree.map(lambda a, m: a + m, vbar, mixed)
            leaves, tdef = jax.tree.flatten(theta_l)
            keys = jax.random.split(jax.random.fold_in(knoise, node),
                                    len(leaves))
            noise = jax.tree.unflatten(tdef, [
                scale * jax.random.normal(kk, a.shape, jnp.float32)
                for kk, a in zip(keys, leaves)])
            zeta = float(traffic["zeta"])
            theta = jax.tree.map(
                lambda t, vb, vv, nn: (
                    t.astype(jnp.float32)
                    + zeta * (vb.astype(jnp.float32) - vv.astype(jnp.float32))
                    + nn).astype(self.store),
                theta_l, vbar, v, noise)
            return theta, v, vbar, jax.tree.map(jnp.add, noise_sum, noise)

        self._node_step = jax.jit(node_step)
        self._mix = jax.jit(mix, static_argnums=0)
        self._finish = jax.jit(finish)

    def _batches(self, data, kround, node):
        dkey = jax.random.fold_in(jax.random.fold_in(kround, DATA_SALT), node)
        n = data["size"][node]
        idx = jax.random.randint(dkey, (int(self.t["local_steps"]),
                                        int(self.t["batch"])), 0, n)
        return {f: jnp.asarray(v[node])[idx] for f, v in data.items()
                if f != "size"}

    def run(self, theta0, data, key, rounds: int, keep=None):
        """Follow ``rounds`` rounds from ``theta0`` (one model, replicated
        to every node) with v = vbar = 0. ``data`` maps each field to a
        ``(K, N, ...)`` array, plus ``size`` ``(K,)``. Returns per round the
        mean loss, the consensus error and, for the rounds in ``keep`` (all
        by default), the params ``(K, ...)`` per leaf and the noise added
        so far, as host arrays."""
        keep = set(range(1, rounds + 1)) if keep is None else set(keep)
        K = self.k
        theta = [jax.tree.map(lambda a: a.astype(self.store), theta0)
                 for _ in range(K)]
        v = [jax.tree.map(jnp.zeros_like, theta[0]) for _ in range(K)]
        vbar = [jax.tree.map(jnp.zeros_like, theta[0]) for _ in range(K)]
        noise_sum = [jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), theta[0])
            for _ in range(K)]
        out = []
        for _ in range(rounds):
            key, kround = jax.random.split(key)
            _, knoise = jax.random.split(kround)
            theta_l, losses, delta = [], [], []
            for k in range(K):
                tl, ls, d = self._node_step(theta[k], v[k],
                                            self._batches(data, kround, k))
                theta_l.append(tl)
                losses.append(ls)
                delta.append(d)
            for k in range(K):
                nb = [j for j in range(K) if self.omega[k, j]]
                mixed = self._mix(tuple(float(self.omega[k, j]) for j in nb),
                                  tuple(delta[j] for j in nb))
                theta[k], v[k], vbar[k], noise_sum[k] = self._finish(
                    theta_l[k], v[k], vbar[k], delta[k], mixed, knoise, k,
                    noise_sum[k])
            del theta_l, delta
            host = [jax.tree.map(np.asarray, t) for t in theta]
            stacked = jax.tree.map(lambda *a: np.stack(a), *host)
            del host
            cons = sum(float(np.sum(np.square(
                a.astype(np.float64) - a.astype(np.float64).mean(0))))
                for a in jax.tree.leaves(stacked)) / K
            r = len(out) + 1
            out.append({
                "loss": float(jnp.mean(jnp.stack(losses))),
                "consensus": cons,
                "params": stacked if r in keep else None,
                "noise": (jax.tree.map(lambda *a: np.stack(
                    [np.asarray(x) for x in a]), *noise_sum)
                    if r in keep else None),
            })
            del stacked
        return out
