"""Device-pool backends: WHERE the per-tick array work runs.

The engine owns state and solver plumbing, the executors own per-tick
control flow, and a DevicePool owns the placement of the heavy array
phases — local training, Algorithm-1 pair estimation, the alpha-mixture
transfer, and the accuracy sweep.  Two backends:

``LocalPool`` (default, ``SimConfig.mesh = 0``)
    The original single-host calls, bit-for-bit (golden-pinned).  Its
    async path additionally implements SUBSET-GATHER training
    (``SimConfig.train_gather``, default on): the clock-eligible lanes
    are gathered into a compact bucket-padded batch for
    ``subset_network_step`` instead of running masked no-op SGD for the
    ineligible majority — per-lane results are identical (lanes keep
    their full-pool PRNG keys), wall clock scales with the eligible
    count, and bucketed widths (powers of two) bound recompilation.

``ShardedPool`` (``SimConfig.mesh = k``)
    The pool axis partitioned over a k-shard 'devices' mesh
    (shard.mesh / shard.ops): per-shard training, pair estimation with
    cross-shard client gather, and the Pallas-kernel transfer.  Padding
    to a shard multiple happens HERE at the pool boundary — NetworkState
    stays exactly pool-sized, so the engine, scenarios and executors are
    completely mesh-agnostic.  A sharded run reproduces the LocalPool
    trajectory field-for-field (parity-tested at mesh-of-1 and an
    emulated mesh-of-8); only placement changes.

Pool padding uses edge replication for array payloads (cheap, and the
padded lanes' outputs are discarded) and False/0 for masks and link
weights, so padded lanes never train, transfer, or contribute energy.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.fl.client import set_client_rows
from repro.fl.divergence import (chunked_pair_lanes,
                                 pairwise_divergence_values)
from repro.fl.divergence import update_divergences as _update_divergences
from repro.fl.transfer import apply_transfer
from repro.sim.faults import PoolFaultError, with_retry
from repro.sim.shard.mesh import DEVICE_AXIS
from repro.sim.training import (mixed_accuracies, network_step,
                                subset_network_step)

if TYPE_CHECKING:                                   # no import cycle
    from repro.sim.engine import SimulationEngine

#: per-shard cap on the vmapped pair-classifier batch (matches the local
#: estimator's pair_chunk so working-set bounds carry over per shard)
PAIR_CHUNK = 256
#: rows of the client stack one row write sets (``write_client_rows``)
ROW_BLOCK = 8


def make_pool(engine: "SimulationEngine") -> "DevicePool":
    n = int(getattr(engine.cfg, "mesh", 0) or 0)
    return ShardedPool(engine, n) if n > 0 else LocalPool(engine)


def _bucket(n: int, cap: int, floor: int = 4) -> int:
    """Smallest power-of-two >= n (configurable floor, default 4),
    capped at the pool size — the static widths the compact subset step
    compiles for.  The floor is ``SimConfig.train_gather_floor`` on the
    training path: a higher floor trades padded lanes for fewer distinct
    compiled widths."""
    w = max(1, int(floor))
    while w < n:
        w *= 2
    return max(1, min(w, cap))


def _gather_pair_rows(clients, pi, pj, width_for):
    """Row-targeted gather for a small pair subset: compact the client
    arrays down to the UNIQUE device rows the pairs touch (padded to
    ``width_for(n_rows)`` by repeating the first row, so bucketed widths
    bound recompilation) and remap the pair indices into the compact
    array.

    Lanes are untouched — each pair still reads exactly its own two
    devices' rows — so per-pair values are bitwise identical to staging
    the full pool; only the data volume entering the computation (and,
    sharded, crossing the interconnect) shrinks from P rows to the
    handful a budgeted refresh names.  Returns (compact_clients, ri, rj)
    with ri/rj int32 indices into the compact row axis."""
    rows, inv = np.unique(np.concatenate([pi, pj]), return_inverse=True)
    ri = inv[:len(pi)].astype(np.int32)
    rj = inv[len(pi):].astype(np.int32)
    width = width_for(len(rows))
    if width < len(rows):
        raise ValueError(f"width {width} < {len(rows)} gathered rows")
    pad = width - len(rows)
    if pad:
        rows = np.concatenate([rows, np.full(pad, rows[0], rows.dtype)])
    gather = jnp.asarray(rows)
    sub = jax.tree_util.tree_map(lambda a: a[gather], clients)
    return sub, ri, rj


class DevicePool:
    """Backend API.  All methods take/return POOL-sized arrays; any
    padding or placement is internal to the backend."""

    name = "base"

    def __init__(self, engine: "SimulationEngine"):
        self.engine = engine
        #: the row write, built for the placed stack's sharding
        self._row_write = None
        #: widths the row-targeted refresh ran, per axis ("rows", "lanes")
        self._widths: dict = {}

    # The public phase methods are TEMPLATE METHODS: they bracket the
    # backend implementation (``_train`` / ``_train_async`` /
    # ``_transfer`` / ``_accuracies``) with the engine's TraceRecorder —
    # start/stop collapse to attribute reads when tracing is off, and
    # ``stop(..., block=out)`` blocks on the phase outputs when it is
    # on, so async dispatch cannot attribute one phase's device time to
    # the next.  Backends override ONLY the underscored hooks.

    # -- full/masked training step (sync round; async masked fallback)
    def train(self, params, clients, key, active, train_mask=None):
        span = self.engine.trace.start("train")
        out = self._train(params, clients, key, active, train_mask)
        self.engine.trace.stop(span, block=out,
                               n_devices=clients.n_devices)
        return out

    # -- async tick: refresh params/eps/acc for the eligible lanes only
    def train_async(self, params, clients, key, active, elig,
                    eps_prev, acc_prev):
        span = self.engine.trace.start("train")
        out = self._train_async(params, clients, key, active, elig,
                                eps_prev, acc_prev)
        self.engine.trace.stop(span, block=out,
                               n_devices=clients.n_devices)
        return out

    def update_divergences(self, div, clients, key, pairs, *, ema=0.0,
                           keys=None, h0=None):
        cfg = self.engine.cfg
        span = self.engine.trace.start("divergence")
        out = _update_divergences(
            div, self.engine.pair_inputs(clients), key, pairs,
            tau=cfg.div_tau, T=cfg.div_T, batch=cfg.batch, lr=cfg.lr,
            ema=ema, values_fn=self._values_fn(), keys=keys, h0=h0,
            client=self.engine.client)
        self.engine.trace.stop(span, block=out,
                               n_devices=clients.n_devices,
                               n_pairs=len(pairs))
        return out

    def refresh_divergences(self, div, clients, key, pairs, *, ema=0.0,
                            keys=None, h0=None):
        """Budgeted drift refresh: same contract as
        ``update_divergences`` but executed through the ROW-TARGETED
        values path — only the rows of the devices the pairs actually
        touch are gathered/staged (the full path stages, and sharded
        all-gathers, the whole pool to serve any pair).  Values are
        bitwise identical; use this when the pair set is a small
        targeted subset (a drift refresh), the full path when it spans
        the pool (the bootstrap).  ``keys``/``h0`` forward the
        content-addressed-key override (see estimate_divergences)."""
        cfg = self.engine.cfg
        span = self.engine.trace.start("divergence")
        out = _update_divergences(
            div, self.engine.pair_inputs(clients), key, pairs,
            tau=cfg.div_tau, T=cfg.div_T, batch=cfg.batch, lr=cfg.lr,
            ema=ema, values_fn=self._targeted_values_fn(), keys=keys,
            h0=h0, client=self.engine.client)
        self.engine.trace.stop(span, block=out,
                               n_devices=clients.n_devices,
                               n_pairs=len(pairs))
        return out

    def transfer(self, params, alpha, psi):
        span = self.engine.trace.start("transfer")
        out = self._transfer(params, alpha, psi)
        self.engine.trace.stop(span, block=out, n_devices=len(psi))
        return out

    def accuracies(self, params, clients):
        span = self.engine.trace.start("eval")
        out = self._accuracies(params, clients)
        self.engine.trace.stop(span, block=out,
                               n_devices=clients.n_devices)
        return out

    def place_clients(self, clients):
        """Where the client stack lives between ticks; the engine passes
        every freshly stacked ``StackedClients`` through here.  The base
        keeps it on the default device."""
        return clients

    def place_frozen(self, frozen):
        """Where the client model's frozen part lives: placed once, every
        phase reads it un-batched.  The base keeps it where it is."""
        return frozen

    def _model(self) -> dict:
        return dict(client=self.engine.client, frozen=self.engine.frozen)

    def write_client_rows(self, clients, rows, block):
        """Write the changed devices into the placed stack: ``block``
        (``fl.client.pad_clients`` of those devices, padded to the
        stack's ``n_max``) becomes rows ``rows`` of ``clients``.  One
        jitted write donates the stack, so the rows land in its
        buffers; a placed (committed) stack keeps its sharding, an
        unplaced one stays unplaced.  The writes go in fixed blocks of
        ``ROW_BLOCK`` rows, so the write compiles once; the last block
        repeats its last row, with that row's own data."""
        if self._row_write is None:
            placed = all(a.committed
                         for a in jax.tree_util.tree_leaves(clients))
            pin = dict(out_shardings=jax.tree_util.tree_map(
                lambda a: a.sharding, clients)) if placed else {}
            self._row_write = jax.jit(set_client_rows, donate_argnums=0,
                                      **pin)
        rows = np.asarray(rows, np.int32)
        for s in range(0, len(rows), ROW_BLOCK):
            take = np.minimum(np.arange(s, s + ROW_BLOCK), len(rows) - 1)
            clients = self._row_write(
                clients, rows[take],
                jax.tree_util.tree_map(lambda a: a[take], block))
        return clients

    # -------------------------------------------------- backend hooks
    def _train(self, params, clients, key, active, train_mask=None):
        raise NotImplementedError

    def _train_async(self, params, clients, key, active, elig,
                     eps_prev, acc_prev):
        raise NotImplementedError

    def _transfer(self, params, alpha, psi):
        raise NotImplementedError

    def _accuracies(self, params, clients):
        raise NotImplementedError

    # ------------------------------------------------------ fault gate
    def _fault_gate(self, params):
        """Consume this tick's injected pool faults before a heavy op
        (both pools call it entering their training phase — the tick's
        first pool op).  A lost shard is detected and recovered
        (backend-specific ``_recover_shard``); transient op failures are
        ridden out with bounded retry + exponential backoff.  No
        injector installed -> nothing to consume, zero overhead.

        Takes and returns the params tree: shard recovery re-seeds the
        lost devices through ``engine.state.params``, and the caller's
        already-captured argument must not shadow that update."""
        eng = self.engine
        inj = eng.faults
        if inj is None:
            return params
        shard = inj.take_lost_shard()
        if shard is not None:
            eng.state.params = params
            self._recover_shard(shard)
            params = eng.state.params
        if inj.pending_op_failures > 0:
            def attempt():
                if inj.op_attempt_fails():
                    raise PoolFaultError(
                        "injected transient pool-op failure")
            with_retry(attempt, retries=eng.cfg.fault_retries,
                       backoff_s=eng.cfg.fault_backoff_s)
        return params

    def _recover_shard(self, shard: int):
        """Backend hook: bring a lost shard's devices back.  LocalPool
        is one host with no shards, so the injector never schedules a
        shard loss against it (``n_shards`` reads 0) and this is never
        reached; ShardedPool overrides."""

    def _values_fn(self):
        """Hook into fl.divergence.estimate_divergences; None = local."""
        return None

    def _targeted_values_fn(self):
        """Row-targeted variant of ``_values_fn`` (budgeted refreshes)."""
        raise NotImplementedError

    def _width(self, axis: str, n: int, bucket) -> int:
        """Width of the row-targeted refresh's ``axis`` ("rows" gathered
        or pair "lanes") for ``n``: the smallest width it already ran
        that holds ``n``, else ``bucket(n)``.  A refresh below the
        widths seen so far, such as the last few dirty pairs of a drift,
        then reuses their programs instead of compiling its own."""
        seen = self._widths.setdefault(axis, set())
        w = min((s for s in seen if s >= n), default=None)
        if w is None:
            w = bucket(n)
            seen.add(w)
        return w

    # shared async merge: measurements refresh ONLY where a device ticked
    def _merge_measured(self, g, eps_g, acc_g, eps_prev, acc_prev):
        """``eps_g``/``acc_g``: the fresh values FOR the lanes in ``g``
        (same order, length len(g))."""
        eps_out = np.array(eps_prev, float, copy=True)
        acc_out = np.array(acc_prev, float, copy=True)
        eps_out[g] = np.asarray(eps_g, float)
        acc_out[g] = np.asarray(acc_g, float)
        return eps_out, acc_out


class LocalPool(DevicePool):
    """Single host: the pre-pool engine behavior, bit-for-bit."""

    name = "local"

    def _train(self, params, clients, key, active, train_mask=None):
        cfg = self.engine.cfg
        params = self._fault_gate(params)
        mask = None if train_mask is None else jnp.asarray(train_mask)
        return network_step(params, clients, key, jnp.asarray(active),
                            mask, iters=cfg.train_iters, batch=cfg.batch,
                            lr=cfg.lr, **self._model())

    def _train_async(self, params, clients, key, active, elig,
                     eps_prev, acc_prev):
        cfg = self.engine.cfg
        params = self._fault_gate(params)
        g = np.flatnonzero(np.logical_and(active, elig))
        if not cfg.train_gather:
            # masked full-pool path: every lane computes, ineligible
            # results are discarded (the pre-subset-gather behavior,
            # kept as the parity reference; _train, not train — the
            # template wrapper already timed this call)
            params, eps, acc = self._train(params, clients, key, active,
                                           elig)
            eps_out, acc_out = self._merge_measured(
                g, np.asarray(eps, float)[g], np.asarray(acc, float)[g],
                eps_prev, acc_prev)
            return params, eps_out, acc_out
        if len(g) == 0:                 # nobody's clock fired
            return params, np.array(eps_prev, float, copy=True), \
                np.array(acc_prev, float, copy=True)
        # compact gather: lane i keeps the key split(key, P)[i] it would
        # have had in the masked step, so per-device results are bitwise
        # identical — only the no-op lanes disappear
        keys = jax.random.split(key, clients.n_devices)
        w = _bucket(len(g), clients.n_devices,
                    cfg.train_gather_floor)
        gpad = np.concatenate([g, np.full(w - len(g), g[0], g.dtype)])
        gj = jnp.asarray(gpad)
        sub = lambda a: a[gj]                                 # noqa: E731
        trained, eps_s, acc_s = subset_network_step(
            jax.tree_util.tree_map(sub, params),
            jax.tree_util.tree_map(sub, clients),
            keys[gj], jnp.asarray(active)[gj],
            iters=cfg.train_iters, batch=cfg.batch, lr=cfg.lr,
            **self._model())
        k = len(g)
        gi = jnp.asarray(g)
        params = jax.tree_util.tree_map(
            lambda p, t: p.at[gi].set(t[:k]), params, trained)
        eps_out, acc_out = self._merge_measured(
            g, np.asarray(eps_s, float)[:k], np.asarray(acc_s, float)[:k],
            eps_prev, acc_prev)
        return params, eps_out, acc_out

    def _transfer(self, params, alpha, psi):
        return apply_transfer(params, jnp.asarray(alpha),
                              jnp.asarray(psi))

    def _accuracies(self, params, clients):
        return mixed_accuracies(params, clients, **self._model())

    def _targeted_values_fn(self):
        """Single-host row targeting: one bucketed row gather for the
        whole pair batch (the compact clients replace the full (P,
        n_max, ...) stack inside the vmapped pair kernel), rows and pair
        lanes padded to a width already run (``_width``) or a power of
        two, so compilations stay bounded as the dirty count wanders
        under the budget."""
        def values(h0, clients, pi, pj, keys, *, tau, T, batch, lr):
            sub, ri, rj = _gather_pair_rows(
                clients, pi, pj, lambda r: self._width(
                    "rows", r, lambda r: _bucket(r, clients.n_devices)))

            def call(ci, cj, ck):
                return pairwise_divergence_values(
                    h0, sub, jnp.asarray(ci, jnp.int32),
                    jnp.asarray(cj, jnp.int32), ck,
                    tau=tau, T=T, batch=batch, lr=lr,
                    client=self.engine.client)

            return chunked_pair_lanes(
                ri, rj, keys, self._width(
                    "lanes", len(ri), lambda n: _bucket(n, PAIR_CHUNK)),
                call, pad_partial=True)
        return values


class ShardedPool(DevicePool):
    """Pool axis over a 'devices' mesh; see the module docstring."""

    def __init__(self, engine: "SimulationEngine", n_shards: int):
        super().__init__(engine)
        from repro.sim.shard import mesh as mesh_lib, ops
        self.mesh = mesh_lib.make_pool_mesh(n_shards)
        self.n_shards = self.mesh.shape[DEVICE_AXIS]
        self.name = f"sharded-{self.n_shards}"
        cfg = engine.cfg
        client = engine.client
        self._train_fn = ops.build_train_step(
            self.mesh, iters=cfg.train_iters, batch=cfg.batch, lr=cfg.lr,
            client=client)
        self._pair_fn = ops.build_pair_values(
            self.mesh, tau=cfg.div_tau, T=cfg.div_T, batch=cfg.batch,
            lr=cfg.lr, client=client)
        self._transfer_fn = ops.build_transfer(self.mesh)
        self._acc_fn = ops.build_accuracies(self.mesh, client)

    def place_clients(self, clients):
        """Shard the client stack over the pool mesh once, so each chip
        holds its own block instead of every call copying the whole
        stack out of the default device.  A pool that does not divide
        the shard count stays put: its calls pad, then shard."""
        if self._pad(clients.n_devices):
            return clients
        return jax.device_put(clients, NamedSharding(
            self.mesh, PartitionSpec(DEVICE_AXIS)))

    def place_frozen(self, frozen):
        """Replicated over the pool mesh, once."""
        return jax.device_put(frozen, NamedSharding(self.mesh,
                                                    PartitionSpec()))

    # ------------------------------------------------------ pool padding
    def _pad(self, n: int) -> int:
        return -n % self.n_shards

    def _pad_tree(self, tree, pad: int):
        if not pad:
            return tree
        return jax.tree_util.tree_map(
            lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                              mode="edge"), tree)

    @staticmethod
    def _pad_mask(m, pad: int):
        return np.concatenate([np.asarray(m, bool), np.zeros(pad, bool)]) \
            if pad else np.asarray(m, bool)

    def _unpad_tree(self, tree, n: int, pad: int):
        if not pad:
            return tree
        return jax.tree_util.tree_map(lambda a: a[:n], tree)

    # ------------------------------------------------- shard membership
    def shard_devices(self, s: int):
        """Pool indices shard ``s`` owns (the pool axis is
        block-partitioned over the padded pool; padded lanes excluded)."""
        n = self.engine.state.pool_size
        blk = (n + self._pad(n)) // self.n_shards
        return list(range(s * blk, min((s + 1) * blk, n)))

    def _recover_shard(self, s: int):
        """A shard died: its devices' on-device training state is gone,
        but the host-side NetworkState survives — so instead of killing
        the run, the shard's ACTIVE devices re-enter through the
        engine's churn/reseed path (params re-seeded from the solved
        source mixture, assignment marked dirty for a membership
        re-solve).  See engine._recover_devices."""
        devs = [d for d in self.shard_devices(s)
                if bool(self.engine.state.active[d])]
        if devs:
            self.engine._recover_devices(devs, shard=s)

    # ------------------------------------------------------------ phases
    def _train(self, params, clients, key, active, train_mask=None):
        cfg = self.engine.cfg
        params = self._fault_gate(params)
        n = clients.n_devices
        pad = self._pad(n)
        keys = jax.random.split(key, n)     # the single-host key stream
        mask = np.ones(n, bool) if train_mask is None \
            else np.asarray(train_mask, bool)
        out, eps, acc = self._train_fn(
            self.engine.frozen,
            self._pad_tree(params, pad), self._pad_tree(clients, pad),
            self._pad_tree(keys, pad),
            jnp.asarray(self._pad_mask(active, pad)),
            jnp.asarray(self._pad_mask(mask, pad)))
        return self._unpad_tree(out, n, pad), eps[:n], acc[:n]

    def _train_async(self, params, clients, key, active, elig,
                     eps_prev, acc_prev):
        # under SPMD the masked lanes are free (they run on the shards
        # that own them either way), so the sharded pool keeps the
        # one-call masked step rather than a gather whose indices would
        # change the compiled program every tick
        g = np.flatnonzero(np.logical_and(active, elig))
        params, eps, acc = self._train(params, clients, key, active,
                                       elig)
        eps_out, acc_out = self._merge_measured(
            g, np.asarray(eps, float)[g], np.asarray(acc, float)[g],
            eps_prev, acc_prev)
        return params, eps_out, acc_out

    def _values_fn(self):
        def values(h0, clients, pi, pj, keys, *, tau, T, batch, lr):
            del tau, T, batch, lr           # baked into _pair_fn at init
            cp = self._pad_tree(clients, self._pad(clients.n_devices))
            # pair-axis chunking: per-shard width w (<= PAIR_CHUNK), so
            # a 4-pair gossip tick pads to one lane per shard while an
            # all-pairs bootstrap streams full chunks; pad_partial — the
            # lanes must always divide the mesh
            w = min(PAIR_CHUNK, -(-len(pi) // self.n_shards))

            def call(ci, cj, ck):
                return self._pair_fn(h0, cp, jnp.asarray(ci, jnp.int32),
                                     jnp.asarray(cj, jnp.int32), ck)

            return chunked_pair_lanes(pi, pj, keys, w * self.n_shards,
                                      call, pad_partial=True)
        return values

    def _targeted_values_fn(self):
        """Sharded row targeting: the compact row set (bucketed, padded
        to a shard multiple) is what gets device-sharded and
        ALL-GATHERED inside ``build_pair_values`` — the cross-shard
        gather shrinks from the whole padded pool to just the rows this
        refresh touches, which is the row-targeted-gather headroom noted
        when the sharding PR closed.  Rows and lanes take widths as the
        single-host path does (``_width``)."""
        def values(h0, clients, pi, pj, keys, *, tau, T, batch, lr):
            del tau, T, batch, lr           # baked into _pair_fn at init
            sub, ri, rj = _gather_pair_rows(
                clients, pi, pj, lambda r: self._width(
                    "rows", r, lambda r: -(-_bucket(r, clients.n_devices)
                                           // self.n_shards)
                    * self.n_shards))
            lanes = self._width("lanes", len(ri), lambda n: _bucket(
                n, PAIR_CHUNK * self.n_shards))
            w = min(PAIR_CHUNK, -(-lanes // self.n_shards))

            def call(ci, cj, ck):
                return self._pair_fn(h0, sub, jnp.asarray(ci, jnp.int32),
                                     jnp.asarray(cj, jnp.int32), ck)

            return chunked_pair_lanes(ri, rj, keys, w * self.n_shards,
                                      call, pad_partial=True)
        return values

    def _transfer(self, params, alpha, psi):
        n = len(psi)
        pad = self._pad(n)
        a = np.asarray(alpha, np.float32)
        s = np.asarray(psi, np.float32)
        if pad:
            a = np.pad(a, ((0, pad), (0, pad)))    # zero links: padded
            s = np.pad(s, (0, pad))                # lanes keep their own
        out = self._transfer_fn(self._pad_tree(params, pad),
                                jnp.asarray(a), jnp.asarray(s))
        return self._unpad_tree(out, n, pad)

    def _accuracies(self, params, clients):
        n = clients.n_devices
        pad = self._pad(n)
        return self._acc_fn(self.engine.frozen, self._pad_tree(params, pad),
                            self._pad_tree(clients, pad))[:n]
