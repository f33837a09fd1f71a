"""Algorithm 1 — decentralized federated estimation of the empirical
H-divergence for every device pair.

Per pair (i, j): relabel device-i data as class 0 and device-j data as
class 1; both devices train a shared-initialization binary domain classifier
locally for T^d iterations; exchange parameters and average; repeat tau^d
times; the averaged classifier's domain-classification error eps on the
union maps to the empirical divergence

    d_H(D_i, D_j) = 2 (1 - 2 eps)        (separability; clipped at 0)

Only classifier parameters ever cross the link — the FL privacy property.

All N(N-1)/2 pairs train simultaneously under one vmapped lax.scan (the
pairwise parameter exchange is a collective_permute between the two pair
members on a real pod; under vmap it is the pairwise average below).

The module has grown three orthogonal axes since the one-shot estimator,
each with an invariant the simulator's parity guarantees rest on:

INCREMENTAL (``pairs`` / ``update_divergences``)
    Estimate/refresh an explicit pair subset instead of all pairs; the
    merge back into the running (N, N) matrix is a symmetric scatter
    with an optional per-pair EMA weight on the old value.  The solver
    never sees a half-updated matrix: callers get a merged copy.

CHUNKED (``pair_chunk`` / ``chunked_pair_lanes``)
    The pair axis is driven in fixed-width padded chunks so thousands
    of vmapped pair-classifiers compile once and bound their stacked
    working set.  Pad lanes repeat a real pair and their outputs are
    discarded — padding never changes a value.

RELOCATABLE (``pair_keys`` / ``values_fn``)
    Each pair's estimate depends only on its own (i, j, key) lane.  The
    per-pair key schedule and the canonical (min, max) pair order are
    fixed HERE, before any chunking or sharding, so any backend that
    keeps lanes intact — a different chunk width, the mesh-sharded
    pool, a row-targeted gather — reproduces the local values
    bit-for-bit.

``budget_pairs`` (bottom) is the drift-aware scheduling companion: given
pairs whose estimates were invalidated by feature drift, it ranks them
stalest-first and truncates to a per-tick budget — the simulator
re-measures the most out-of-date links first instead of all pairs.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.client import CNNClient, PairInputs


def _client(client):
    return CNNClient() if client is None else client


@functools.partial(jax.jit, static_argnames=("tau", "T", "batch", "lr",
                                             "client"))
def pairwise_divergence_values(h0, clients: PairInputs, pair_i, pair_j,
                               keys, *, tau: int, T: int, batch: int,
                               lr: float, client=None):
    """h0: single init of the client's 2-class hypothesis (shared h');
    ``clients``: the rows it classifies (``PairInputs``: ``x`` and
    ``counts``; a ``StackedClients`` serves the CNN).  pair_i/j: (P,) int32;
    ``keys``: per-pair PRNG keys, (P, key_dim) — see ``pair_keys``.  Each
    pair's estimate depends only on its own (i, j, key) lane, so callers
    are free to re-chunk or shard the pair axis (the mesh-sharded pool
    does exactly that) without changing any value.  ``batch`` counts
    samples: a step draws ``batch`` rows, or for a token client
    ``batch * tokens_per_sample`` token rows, from each device."""
    client = _client(client)
    binary_loss = client.pair_loss
    rows = batch * max(client.tokens_per_sample, 1)
    n_dev, n_max = clients.x.shape[0], clients.x.shape[1]
    flat_x = jnp.reshape(clients.x, (n_dev * n_max,) + clients.x.shape[2:])

    def one_pair(i, j, k):
        hi = h0
        hj = h0

        def step(carry, inputs):
            hi, hj = carry
            t, kt = inputs
            ki, kj = jax.random.split(kt)
            ridx_i = jax.random.randint(ki, (rows,), 0, clients.counts[i])
            ridx_j = jax.random.randint(kj, (rows,), 0, clients.counts[j])
            xi = flat_x[i * n_max + ridx_i]
            xj = flat_x[j * n_max + ridx_j]
            gi = jax.grad(binary_loss)(hi, xi, jnp.zeros(rows, jnp.int32))
            gj = jax.grad(binary_loss)(hj, xj, jnp.ones(rows, jnp.int32))
            hi = jax.tree_util.tree_map(lambda a, g: a - lr * g, hi, gi)
            hj = jax.tree_util.tree_map(lambda a, g: a - lr * g, hj, gj)
            # parameter exchange + average every T local iterations
            sync = (t + 1) % T == 0
            avg = jax.tree_util.tree_map(lambda a, b: 0.5 * (a + b), hi, hj)
            hi = jax.tree_util.tree_map(
                lambda a, m: jnp.where(sync, m, a), hi, avg)
            hj = jax.tree_util.tree_map(
                lambda a, m: jnp.where(sync, m, a), hj, avg)
            return (hi, hj), None

        keys = jax.random.split(k, tau * T)
        (hi, hj), _ = jax.lax.scan(step, (hi, hj),
                                   (jnp.arange(tau * T), keys))
        hbar = jax.tree_util.tree_map(lambda a, b: 0.5 * (a + b), hi, hj)

        # error of hbar on the union (device i -> 0, device j -> 1)
        row = jnp.arange(n_max)

        def dev_err(d, lab):
            x = flat_x[d * n_max + row]
            pred = jnp.argmax(client.pair_logits(hbar, x), axis=-1)
            valid = row < clients.counts[d]
            wrong = jnp.logical_and(valid, pred != lab)
            return jnp.sum(wrong.astype(jnp.float32)), \
                jnp.sum(valid.astype(jnp.float32))

        wi, ni = dev_err(i, 0)
        wj, nj = dev_err(j, 1)
        eps = (wi + wj) / jnp.maximum(ni + nj, 1.0)
        return jnp.clip(2.0 * (1.0 - 2.0 * eps), 0.0, 2.0)

    with jax.named_scope("pair_scan"):
        return jax.vmap(one_pair)(pair_i, pair_j, keys)


def pair_keys(key, npairs: int, pair_chunk: int = 256):
    """The per-pair PRNG keys of the local chunked estimator, as one
    (npairs, key_dim) array.

    Key schedule: when everything fits in one chunk
    (``npairs <= pair_chunk``) the keys are simply
    ``split(key, npairs)`` — the historical single-call stream.  Beyond
    that, chunk c (pairs [c0, c0 + pair_chunk)) draws
    ``split(fold_in(key, c0), pair_chunk)`` and pair p's key is its lane
    of its chunk's split.  Chunk boundaries are part of the schedule —
    which is exactly why this function exists: it is THE schedule,
    computed once by ``estimate_divergences`` and handed to whichever
    backend executes the lanes (local chunk loop, mesh-sharded pool,
    row-targeted refresh).  Backends may re-chunk, pad, or shard the
    (i, j, key) lanes freely; because no backend ever derives keys
    itself, every backend reproduces the local values bit-for-bit."""
    if npairs <= pair_chunk:
        return jax.random.split(key, npairs)
    out = [jax.random.split(jax.random.fold_in(key, c0), pair_chunk)
           for c0 in range(0, npairs, pair_chunk)]
    return jnp.concatenate(out)[:npairs]


def chunked_pair_lanes(pi, pj, keys, width: int, call, *,
                       pad_partial: bool) -> np.ndarray:
    """Drive ``call(ci, cj, ck) -> (width or fewer,) values`` over
    fixed-width chunks of the pair axis, padding short chunks with
    repeats of their first lane (outputs discarded) so one compilation
    serves every chunk.  The single chunk/pad/truncate implementation
    behind BOTH pair-estimation backends — the local chunk loop and the
    sharded pool's mesh-width chunks — so the key/pad conventions the
    bit-for-bit parity guarantee rests on cannot drift apart.

    ``pad_partial``: True pads even a lone short chunk (the sharded pool
    must divide its lanes over the mesh); False keeps the historical
    local behavior of compiling a small batch at its natural size."""
    npairs = len(pi)
    keys = np.asarray(keys)     # sliced and padded on the host: no program
    out = np.zeros(npairs)
    for c0 in range(0, npairs, width):
        ci = pi[c0:c0 + width]
        cj = pj[c0:c0 + width]
        ck = keys[c0:c0 + width]
        pad = (width - len(ci)) if (pad_partial or npairs > width) else 0
        if pad:
            ci = np.concatenate([ci, np.full(pad, ci[0])])
            cj = np.concatenate([cj, np.full(pad, cj[0])])
            ck = np.concatenate([ck, np.repeat(ck[:1], pad, axis=0)])
        vals = np.asarray(call(ci, cj, ck))
        out[c0:c0 + width - pad] = vals[:width - pad]
    return out


def _chunked_pair_values(h0, clients: PairInputs, pi, pj, keys, *,
                         tau: int, T: int, batch: int, lr: float,
                         pair_chunk: int, client=None) -> np.ndarray:
    """Local (single-host) pair estimation: one vmapped call for small
    batches, fixed-width padded chunks beyond ``pair_chunk``."""
    def call(ci, cj, ck):
        return pairwise_divergence_values(
            h0, clients, jnp.asarray(ci), jnp.asarray(cj), ck,
            tau=tau, T=T, batch=batch, lr=lr, client=client)

    return chunked_pair_lanes(pi, pj, keys, pair_chunk, call,
                              pad_partial=False)


def estimate_divergences(clients: PairInputs, key, *, tau: int = 4,
                         T: int = 25, batch: int = 10, lr: float = 0.01,
                         pairs=None, pair_chunk: int = 256,
                         values_fn=None, keys=None,
                         h0=None, client=None) -> np.ndarray:
    """Algorithm 1: returns the symmetric (N, N) matrix of empirical
    d_H estimates (diagonal 0).

    ``pairs``: optional (P, 2) int array of device pairs to estimate; the
    default is every upper-triangle pair.  Restricting pairs is the
    incremental path — when a simulator round only changed device k's
    data, the N-1 pairs touching k are re-estimated instead of all
    N(N-1)/2 (entries of unrequested pairs are left at 0; merge with
    ``update_divergences``).

    ``pair_chunk``: large networks vmap thousands of pair-classifiers;
    chunking bounds the stacked-parameter working set (chunks are padded
    to a fixed width so one compilation serves every full chunk).

    ``values_fn``: optional executor for the per-pair values,
    ``fn(h0, clients, pi, pj, keys, tau=, T=, batch=, lr=) -> (npairs,)``
    — the placement hook.  The mesh-sharded device pool passes one that
    runs the same lanes under shard_map (cross-shard client gather);
    the budgeted drift refresh passes one that first gathers just the
    rows of the devices the pairs actually touch.  The contract: treat
    (pi, pj, keys) as opaque aligned lanes, return one value per lane
    in order.  The key schedule (``pair_keys``), the shared classifier
    init ``h0``, and the canonicalized (min, max) pair order are fixed
    HERE — a values_fn that keeps lanes intact reproduces the local
    values bit-for-bit, which the parity tests pin.

    ``keys`` / ``h0``: optional EXPLICIT per-pair keys ((npairs,
    key_dim), aligned with the given ``pairs`` order) and classifier
    init, overriding the positional ``pair_keys`` schedule and the
    per-call init drawn from ``key``.  The simulator's drift refresh
    passes CONTENT-ADDRESSED keys (derived from the pair's device ids,
    not its batch position) plus a per-run ``h0``, which makes an
    estimate a deterministic function of (pair identity, pair data):
    re-measuring an unchanged pair reproduces its previous value
    exactly, and the measured value never depends on which batch or
    round the scheduler happened to put the pair in.  When both are
    given ``key`` may be None."""
    n = clients.n_devices
    if pairs is None:
        pi, pj = np.triu_indices(n, k=1)
    else:
        pairs = np.atleast_2d(np.asarray(pairs, np.int32))
        if pairs.size == 0:
            return np.zeros((n, n))
        pi, pj = np.minimum(pairs[:, 0], pairs[:, 1]), \
            np.maximum(pairs[:, 0], pairs[:, 1])
    if keys is not None and len(keys) != len(pi):
        raise ValueError(f"explicit keys: {len(keys)} lanes for "
                         f"{len(pi)} pairs")
    if keys is None or h0 is None:
        key, init_key = jax.random.split(key)
        if h0 is None:
            h0 = _client(client).pair_init(init_key)
        if keys is None:
            keys = pair_keys(key, len(pi), pair_chunk)

    if values_fn is not None:
        d = np.asarray(values_fn(h0, clients, pi, pj, keys,
                                 tau=tau, T=T, batch=batch, lr=lr))
    else:
        d = _chunked_pair_values(h0, clients, pi, pj, keys, tau=tau, T=T,
                                 batch=batch, lr=lr, pair_chunk=pair_chunk,
                                 client=client)
    out = np.zeros((n, n))
    out[pi, pj] = d
    out[pj, pi] = d
    return out


def update_divergences(div: np.ndarray, clients: PairInputs, key,
                       pairs, *, tau: int = 4, T: int = 25, batch: int = 10,
                       lr: float = 0.01, ema=0.0, values_fn=None,
                       keys=None, h0=None, client=None) -> np.ndarray:
    """Incrementally refresh ``div`` on the given (P, 2) pairs only and
    return the merged copy (Algorithm 1 run just for those links) — the
    pair-incremental path every divergence mutation in the simulator
    flows through: the sync bootstrap of never-estimated pairs, the
    async gossip meetings, and the drift-aware budgeted refresh.

    ``ema``: weight given to the OLD value when merging — scalar or
    per-pair (P,) array, applied in the symmetric scatter
    ``out[i, j] = ema * out[i, j] + (1 - ema) * fresh[i, j]``.
    0 (default) replaces outright, the original behavior.  Callers pick
    the weight by what the old value still means:

      * never-estimated pair — no old value to keep: 0
      * repeated gossip meeting on an unchanged link — old value is an
        independent sample of the same quantity: ``div_ema`` averages
        the Algorithm-1 estimator's sampling noise instead of churning
        the solver input
      * drift-dirtied pair — the old value measured a distribution that
        no longer exists: 0 again (keeping any of it would anchor the
        solver to the pre-drift world)

    ``values_fn``, ``keys``, ``h0`` and ``client`` are forwarded to
    ``estimate_divergences`` (the placement hook and the
    content-addressed-key override; see there for both contracts)."""
    pairs = np.atleast_2d(np.asarray(pairs, np.int32))
    out = np.array(div, float, copy=True)
    if pairs.size == 0:
        return out
    fresh = estimate_divergences(clients, key, tau=tau, T=T, batch=batch,
                                 lr=lr, pairs=pairs, values_fn=values_fn,
                                 keys=keys, h0=h0, client=client)
    pi, pj = pairs[:, 0], pairs[:, 1]        # vectorized symmetric scatter
    w = np.broadcast_to(np.asarray(ema, float), pi.shape)
    out[pi, pj] = w * out[pi, pj] + (1.0 - w) * fresh[pi, pj]
    out[pj, pi] = w * out[pj, pi] + (1.0 - w) * fresh[pj, pi]
    return out


def budget_pairs(pairs: np.ndarray, div_tick: np.ndarray,
                 budget: int) -> np.ndarray:
    """Rank candidate ``pairs`` stalest-first and truncate to ``budget``
    — the drift-aware re-estimation schedule.

    ``pairs``: (M, 2) candidate pairs (the simulator passes the dirty
    active pairs).  ``div_tick``: (N, N) tick each pair was last
    estimated (-1: never).  ``budget``: max pairs to return; <= 0 means
    unbounded (every candidate, still in rank order).

    Ordering is (last-estimate tick ascending, i, j) — fully
    deterministic, no RNG: the pair whose estimate is most out of date
    is re-measured first, and ties break on device ids so two runs of
    the same trajectory refresh identical subsets.  Never-estimated
    candidates (tick -1) therefore always outrank once-measured ones,
    which is the right priority: the solver is already substituting a
    prior or a stale value for them."""
    pairs = np.atleast_2d(np.asarray(pairs, np.int32))
    if pairs.size == 0:
        return np.zeros((0, 2), np.int32)
    pi, pj = pairs[:, 0], pairs[:, 1]
    order = np.lexsort((pj, pi, div_tick[pi, pj]))
    if budget > 0:
        order = order[:budget]
    return pairs[order]
