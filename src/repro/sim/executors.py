"""Execution layer: HOW the network advances one global tick.

The engine owns state, scenarios, solver plumbing and metrics; an
Executor owns the per-tick control flow.  Two implementations:

``sync`` (SyncExecutor)
    The original round pipeline, behavior-preserving (parity-tested
    against pre-refactor JSONL output): every active device trains each
    round, never-estimated active pairs run Algorithm 1, the drift gate
    decides a warm re-solve, and the full alpha-mixture transfer is
    applied globally.

``async-gossip`` (AsyncGossipExecutor)
    Devices progress on heterogeneous local clocks (repro.sim.clock):
    only clock-eligible devices train on a given global tick (still ONE
    jitted ``network_step`` call — the ineligible lanes are masked out),
    and instead of a global transfer phase, random gossip pairs meet
    each tick: a meeting pair refreshes its Algorithm-1 divergence
    through ``update_divergences``' pair-incremental path (EMA-merged
    into the running estimate) and exchanges models along the currently
    solved alpha links (an incremental, link-local realization of the
    same mixture the sync engine applies in one shot).  The re-solve
    gate adds a staleness term: when the installed assignment has
    outlived ``resolve_patience`` ticks it is warm re-solved even if the
    sparsely-refreshed measurements alone keep the drift metric under
    threshold (sparse refresh systematically undercounts change, so age
    bounds the error — the classic bounded-staleness rule of async FL).

Measurement semantics under async: ``eps_hat`` / ``own_acc`` only
refresh for devices that actually ticked, so the solver sees exactly the
information a decentralized deployment would have.  Algorithm-1 gossip
traffic is unpriced, matching the sync engine; the energy/transmissions
metrics price the model exchanges of the tick.

Both executors share a drift-aware re-estimation phase
(``_refresh_dirty``): when a scenario drifts a device's features
(``engine.drift_features``), every Algorithm-1 estimate involving that
device is flagged dirty in ``NetworkState.div_dirty``, and each
subsequent tick re-measures a BUDGETED top-K of the dirty active pairs,
stalest first (``SimConfig.div_budget`` / ``div_refresh``), through the
device pool's row-targeted refresh path — so the solver tracks a moving
divergence landscape at a per-tick cost independent of N(N-1)/2.
Scenarios that never drift features keep an empty dirty set and are
bit-for-bit unaffected.

Neither executor touches arrays directly for the heavy phases: training,
divergence estimation, the mixture transfer and the accuracy sweep all
go through ``engine.pool`` (repro.sim.shard.pool), so the same control
flow runs single-host or sharded over a device mesh unchanged.
"""
from __future__ import annotations

import time
from typing import TYPE_CHECKING, Dict, List, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.divergence import budget_pairs
from repro.sim.clock import DeviceClocks
from repro.sim.metrics import RoundRecord
from repro.sim.shard.pool import PAIR_CHUNK

if TYPE_CHECKING:                                   # no import cycle
    from repro.sim.engine import SimulationEngine


@jax.jit
def _fold_pair_keys(base, lo, hi):
    """``fold_in(fold_in(base, lo), hi)``, lane by lane."""
    return jax.vmap(lambda i, j: jax.random.fold_in(
        jax.random.fold_in(base, i), j))(lo, hi)

EXECUTORS: Dict[str, Type["Executor"]] = {}


def register(name: str):
    def deco(cls):
        cls.name = name
        EXECUTORS[name] = cls
        return cls
    return deco


def get_executor(name: str) -> Type["Executor"]:
    if name not in EXECUTORS:
        raise KeyError(f"unknown engine {name!r}; "
                       f"available: {sorted(EXECUTORS)}")
    return EXECUTORS[name]


class Executor:
    """Per-tick control flow over a SimulationEngine's state.  The
    helpers below are the blocks both executors share verbatim; step()
    wires them around the mode-specific training/measurement phases."""

    name = "base"
    #: lazily-measuring executors set this so the engine's divergence
    #: view (solver input, drift metric, re-solve snapshot) substitutes
    #: cfg.div_prior for never-estimated pairs
    divergence_prior_view = False

    def __init__(self, engine: "SimulationEngine"):
        self.engine = engine

    def setup(self):
        """Called once at engine init, before the scenario's setup."""

    def step(self, t: int) -> dict:
        raise NotImplementedError

    # ---------------------------------------------- checkpoint support
    def state_dict(self) -> dict:
        """Executor-owned mutable state for run checkpoints (sync: none
        — its control flow is a pure function of engine state + tick)."""
        return {}

    def load_state_dict(self, state: dict):
        pass

    # --------------------------------------------------- shared phases
    def _begin(self, t: int):
        """Phase 1: scenario mutation (+ restack after data changed).
        Returns (tick start time, scenario events, the tick's counters:
        ``restack_bytes`` and ``restack_rows``, what the restack wrote
        into the client stack, and ``n_rendered``, the devices whose
        alt-domain features were first rendered)."""
        eng = self.engine
        t0 = time.time()
        eng.trace.begin_tick(t)
        span = eng.trace.start("scenario")
        rendered = len(eng._drift_alt)
        events = eng.scenario.step(eng, t)
        n_rendered = len(eng._drift_alt) - rendered
        eng.trace.stop(span, block=eng.state.params)
        restack_bytes = restack_rows = 0
        if eng._dirty_clients:
            span = eng.trace.start("restack")
            restack_bytes, restack_rows = eng._restack()
            eng.trace.stop(span, block=eng.state.clients,
                           nbytes=restack_bytes)
        return t0, events, dict(restack_bytes=int(restack_bytes),
                                restack_rows=int(restack_rows),
                                n_rendered=int(n_rendered),
                                n_featurized=eng.featurize())

    def _gate(self, a: np.ndarray, t: int, drift: float,
              patience: int = 0):
        """The re-solve decision ladder.  ``patience`` > 0 adds the
        bounded-staleness rule (async): re-solve once the installed
        assignment is that many ticks old.  Returns (reason, solve_age);
        reason None means no re-solve."""
        eng, st, cfg = self.engine, self.engine.state, self.engine.cfg
        solve_age = t - eng._solve_tick if st.solver is not None else -1
        membership_changed = eng._membership_dirty or st.solver is None \
            or not np.array_equal(a, st.solve_active)
        if st.solver is None:
            reason = "cold"
        elif membership_changed:
            reason = "membership"
        elif drift > cfg.resolve_threshold:
            reason = "drift"
        elif patience > 0 and solve_age >= patience:
            reason = "staleness"
        else:
            reason = None
        return reason, solve_age

    def _refresh_dirty(self, t: int):
        """Drift-aware divergence re-estimation, shared by both
        executors (runs after the mode's own measurement phase, before
        the re-solve gate).  Under ``div_refresh='dirty'`` (default):
        re-measure a budgeted top-K of the active pairs whose estimates
        feature drift invalidated, stalest first
        (``fl.divergence.budget_pairs``); under ``'all'``: the naive
        reference — every active pair not already measured this tick.
        Re-estimates flow through the pool's ROW-TARGETED refresh path
        and the ``update_divergences`` EMA merge: dirty/never-known
        pairs replace outright (their old value measured a distribution
        that no longer exists), clean pairs caught by 'all' mode
        EMA-merge with ``div_ema``.  Returns (dirty count entering the
        tick, pairs re-estimated).  No dirty pairs -> no work and no
        PRNG consumption, which is what keeps pre-drift scenarios
        golden-parity with this phase compiled in.

        Refresh measurements use CONTENT-ADDRESSED PRNG keys — each
        pair's key derives from its device ids (plus a per-run stream
        and classifier init), not from its position in this tick's
        batch — so an estimate is a deterministic function of (pair
        identity, pair data): re-measuring an unchanged pair reproduces
        its previous value, and WHEN the scheduler got to a pair never
        changes WHAT was measured.  That makes refresh policies
        (budgeted vs. exhaustive) differ only through genuine staleness,
        which is what benchmarks/sim_drift.py measures."""
        eng, st, cfg = self.engine, self.engine.state, self.engine.cfg
        span = eng.trace.start("refresh_select")
        dirty = st.dirty_active_pairs()
        if cfg.div_refresh == "all":
            a = st.active_idx
            ii, jj = np.triu_indices(len(a), k=1)
            pairs = np.stack([a[ii], a[jj]], axis=1).astype(np.int32)
            if len(pairs):                   # already measured this tick
                pairs = pairs[st.div_tick[pairs[:, 0], pairs[:, 1]] < t]
        else:
            budget = len(st.active_idx) if cfg.div_budget < 0 \
                else cfg.div_budget
            pairs = budget_pairs(dirty, st.div_tick, budget)
        if len(pairs) == 0:
            eng.trace.stop(span, n_dirty=len(dirty), n_pairs=0)
            return len(dirty), 0
        pi, pj = pairs[:, 0], pairs[:, 1]
        ema = np.where(
            np.logical_and(st.div_known[pi, pj], ~st.div_dirty[pi, pj]),
            cfg.div_ema, 0.0)
        keys, h0 = self._pair_content_keys(pairs), self._refresh_h0()
        eng.trace.stop(span, block=keys, n_dirty=len(dirty),
                       n_pairs=len(pairs))
        st.div_hat = eng.pool.refresh_divergences(
            st.div_hat, st.clients, None, pairs, ema=ema, keys=keys,
            h0=h0)
        span = eng.trace.start("refresh_select")
        st.mark_pairs_estimated(pairs, t)
        eng.trace.stop(span)
        return len(dirty), len(pairs)

    def _measure_kwargs(self, pairs) -> dict:
        """keys/h0 override for the mode's own measurement phases
        (bootstrap, gossip): empty under the historical 'positional'
        addressing, the content-addressed stream under 'content' — so
        flipping ``div_key_mode`` re-keys EVERY Algorithm-1 measurement
        consistently and re-measuring unchanged data becomes an exact
        no-op across bootstrap/gossip/refresh alike."""
        if self.engine.cfg.div_key_mode != "content":
            return {}
        return dict(keys=self._pair_content_keys(np.asarray(pairs)),
                    h0=self._refresh_h0())

    def _pair_content_keys(self, pairs: np.ndarray) -> np.ndarray:
        """(K, key_dim) content-addressed keys, on the host:
        ``fold_in(fold_in(refresh_stream, min(i, j)), max(i, j))`` —
        symmetric in the pair, independent of batch composition.  They
        are derived in lanes of ``PAIR_CHUNK`` (the last one padded), so
        every K runs the one compiled program."""
        base = jax.random.fold_in(
            jax.random.PRNGKey(self.engine.cfg.seed), 2 ** 20)
        n = len(pairs)
        lanes = np.zeros((2, -(-max(n, 1) // PAIR_CHUNK) * PAIR_CHUNK),
                         np.int32)
        lanes[0, :n] = np.minimum(pairs[:, 0], pairs[:, 1])
        lanes[1, :n] = np.maximum(pairs[:, 0], pairs[:, 1])
        return np.concatenate([
            np.asarray(_fold_pair_keys(base, *lanes[:, s:s + PAIR_CHUNK]))
            for s in range(0, lanes.shape[1], PAIR_CHUNK)])[:n]

    def _refresh_h0(self):
        """The per-run shared classifier init of the refresh stream
        (fixed so refresh measurements are content-addressed; cached —
        it is the same tree every tick)."""
        if not hasattr(self, "_refresh_h0_cache"):
            self._refresh_h0_cache = self.engine.client.pair_init(
                jax.random.fold_in(
                    jax.random.PRNGKey(self.engine.cfg.seed), 2 ** 21))
        return self._refresh_h0_cache

    def _run_solve(self, a: np.ndarray, t: int):
        """Warm-started re-solve + installation.  Returns
        (warm, outer_iters, solve wall seconds)."""
        eng = self.engine
        warm = eng.state.solver is not None
        span = eng.trace.start("solve")
        res = eng._solve(a)
        eng._install_solution(a, res, t)
        eng.trace.stop(span, n_devices=len(a))
        # the row's solver_wall_s keeps the solver's own measurement
        return warm, res.outer_iters, res.solve_time_s

    def _token_counts(self, a: np.ndarray) -> dict:
        """The sync round's trained devices, and the token positions of
        its training (``n_trained * train_iters * batch`` samples) and of
        its three measurement sweeps over the active devices' samples (0
        for a client whose samples are not token sequences)."""
        st, cfg = self.engine.state, self.engine.cfg
        tok = self.engine.client.tokens_per_sample
        n_trained = int(np.sum(st.labeled_devices[a]))
        samples = sum(st.pool[j].n for j in a)
        return dict(n_trained=n_trained,
                    train_tokens=n_trained * cfg.train_iters * cfg.batch
                    * tok, eval_tokens=3 * samples * tok)

    def _link_churn(self) -> float:
        """Jaccard distance of the active-link set vs. the previous
        tick (links = solved alpha above link_thresh)."""
        eng, st, cfg = self.engine, self.engine.state, self.engine.cfg
        links = {(int(i), int(j)) for i, j in zip(
            *np.nonzero(st.alpha > cfg.link_thresh))}
        union = links | eng._prev_links
        churn = len(links ^ eng._prev_links) / max(len(union), 1)
        eng._prev_links = links
        return churn

    def _emit(self, *, t, t0, a, acc, events, resolved, warm,
              solver_iters, solver_wall, drift, energy, transmissions,
              churn, solve_age, reason, n_dirty_pairs=0,
              n_reestimated=0, **extras):
        """Build + log the tick's RoundRecord from the shared fields;
        mode-specific fields come in through ``extras``.  Returns
        (logged row, record)."""
        eng, st, cfg = self.engine, self.engine.state, self.engine.cfg
        src = a[st.psi[a] == 0.0]
        tgt = a[st.psi[a] == 1.0]
        eng._energy_cum += energy
        n_drifted = sum(1 for e in events
                        if e.get("event") == "feature_drift")
        n_faults = eng.faults.n_faults if eng.faults is not None else 0
        n_recov = eng.faults.n_recovered if eng.faults is not None else 0
        record = RoundRecord(
            round=t, scenario=cfg.scenario, n_active=len(a),
            n_sources=len(src), n_targets=len(tgt),
            resolved=bool(resolved), warm=bool(warm),
            solver_iters=int(solver_iters),
            solver_wall_s=float(solver_wall),
            drift=float(drift if np.isfinite(drift) else -1.0),
            mean_target_acc=float(acc[tgt].mean()) if len(tgt)
            else float("nan"),
            mean_source_acc=float(acc[src].mean()) if len(src)
            else float("nan"),
            energy=float(energy),
            energy_cum=float(eng._energy_cum),
            transmissions=int(transmissions),
            link_churn=float(churn), events=events,
            wall_time_s=time.time() - t0,
            engine=self.name, solve_age=int(solve_age),
            resolve_reason=reason, targets=[int(j) for j in tgt],
            # the tick's link set, which _link_churn has just stored
            links=[list(link) for link in sorted(eng._prev_links)],
            n_drifted=int(n_drifted),
            n_dirty_pairs=int(n_dirty_pairs),
            n_reestimated=int(n_reestimated),
            n_faults=int(n_faults), n_recovered=int(n_recov),
            resume_count=int(eng._resume_count),
            n_compiled=int(eng.trace.n_compiled),
            # per-phase wall totals popped from the trace accumulators
            # ({} when tracing is off -> the fields keep their 0.0
            # defaults and golden rows are byte-identical)
            **eng.trace.tick_wall_fields(), **extras)
        span = eng.trace.start("log")
        row = eng.logger.log(record)
        eng.trace.stop(span)
        st.round = t + 1
        return row, record


@register("sync")
class SyncExecutor(Executor):
    """The original synchronous round pipeline (see module docstring)."""

    def step(self, t: int) -> dict:
        eng = self.engine
        st, cfg = eng.state, eng.cfg
        t0, events, counts = self._begin(t)

        # 2. batched train + measure (one compiled call per pool shard)
        k_round = jax.random.fold_in(eng.key, t)
        st.params, eps, acc = eng.pool.train(st.params, st.clients,
                                             k_round, st.active)
        st.eps_hat = np.asarray(eps, float)
        st.own_acc = np.asarray(acc, float)

        # 3. incremental divergence refresh: never-estimated pairs run
        # the full-pool path (a bootstrap spans everyone) ...
        pairs = st.unknown_active_pairs()
        if len(pairs):
            k_div = jax.random.fold_in(k_round, 1)
            st.div_hat = eng.pool.update_divergences(
                st.div_hat, st.clients, k_div, pairs,
                **self._measure_kwargs(pairs))
            st.mark_pairs_estimated(pairs, t)
        # ... then the budgeted drift-aware re-estimation of dirtied
        # pairs through the row-targeted refresh path
        n_dirty, n_reest = self._refresh_dirty(t)

        # 4. drift-gated warm re-solve
        a = st.active_idx
        drift = eng._drift_metric()
        reason, solve_age = self._gate(a, t, drift)
        resolved = reason is not None
        warm, solver_iters, solver_wall = False, 0, 0.0
        if resolved:
            warm, solver_iters, solver_wall = self._run_solve(a, t)

        # 5. transfer + evaluation
        mixed = eng.pool.transfer(st.params, st.alpha, st.psi)
        st.params = mixed                        # targets adopt mixtures
        acc_mixed = np.asarray(eng.pool.accuracies(mixed, st.clients),
                               float)

        churn = self._link_churn()
        row, record = self._emit(
            t=t, t0=t0, a=a, acc=acc_mixed, events=events,
            resolved=resolved, warm=warm, solver_iters=solver_iters,
            solver_wall=solver_wall, drift=drift,
            energy=st.energy.energy(st.alpha),
            transmissions=st.energy.transmissions(
                st.alpha, thresh=cfg.link_thresh),
            churn=churn, solve_age=solve_age, reason=reason,
            n_dirty_pairs=n_dirty, n_reestimated=n_reest, **counts,
            **self._token_counts(a))
        if cfg.verbose:
            print(f"[sim] round {t}: active={len(a)} "
                  f"src={record.n_sources} tgt={record.n_targets} "
                  f"resolve={resolved} ({solver_iters} it, warm={warm}) "
                  f"tgt_acc={record.mean_target_acc:.3f} "
                  f"energy={record.energy:.3f}")
        return row


@register("async-gossip")
class AsyncGossipExecutor(Executor):
    """Event-driven ticks: local clocks + random pairwise gossip (see
    module docstring)."""

    divergence_prior_view = True

    def setup(self):
        eng, cfg = self.engine, self.engine.cfg
        # separate streams so the sync path's RNG draws are untouched
        self.clock_rng = np.random.default_rng(cfg.seed + 2)
        self.gossip_rng = np.random.default_rng(cfg.seed + 3)
        eng.state.clocks = DeviceClocks.sample(
            eng.state.pool_size, cfg.tick_periods, self.clock_rng)
        if cfg.gossip_topology not in ("uniform", "ring", "k-regular"):
            raise ValueError(
                f"unknown gossip_topology {cfg.gossip_topology!r}; "
                "available: uniform, ring, k-regular")
        # structured topologies live on a seeded ring over POOL slots, so
        # the neighborhood structure is stable under churn; the ring is
        # drawn from a dedicated stream so 'uniform' runs keep the
        # historical gossip_rng trajectory untouched
        self._ring = np.random.default_rng(cfg.seed + 4).permutation(
            eng.state.pool_size)

    def state_dict(self) -> dict:
        """The two async RNG streams are the executor's only mutable
        state (clocks live on NetworkState, the ring is seed-derived)."""
        return {"clock_rng": self.clock_rng.bit_generator.state,
                "gossip_rng": self.gossip_rng.bit_generator.state}

    def load_state_dict(self, state: dict):
        self.clock_rng.bit_generator.state = state["clock_rng"]
        self.gossip_rng.bit_generator.state = state["gossip_rng"]

    # ------------------------------------------------------------- gossip
    def _select_pairs(self, active_idx: np.ndarray) -> List[Tuple[int, int]]:
        """Disjoint gossip meetings among the active devices, drawn from
        ``cfg.gossip_topology``:

        ``uniform``    random disjoint pairs (the historical default)
        ``ring``       a block of adjacent edges of the seeded ring,
                       restricted to active devices, starting at a
                       random offset each tick
        ``k-regular``  random disjoint edges of the seeded circulant
                       graph (ring neighbors at hops 1..degree/2)

        The pair count is held constant across ticks (``gossip_pairs``,
        default n_active // 4) so the vmapped pair-divergence kernel
        compiles once; when the active set is too small the count
        shrinks to n_active // 2."""
        cfg = self.engine.cfg
        g = cfg.gossip_pairs if cfg.gossip_pairs > 0 \
            else max(len(active_idx) // 4, 1)
        g = min(g, len(active_idx) // 2)
        if g < 1:
            return []
        if cfg.gossip_topology == "uniform":
            perm = self.gossip_rng.permutation(active_idx)
            return [(int(perm[2 * k]), int(perm[2 * k + 1]))
                    for k in range(g)]
        act = set(int(i) for i in active_idx)
        ring = [int(d) for d in self._ring if int(d) in act]
        n = len(ring)
        if cfg.gossip_topology == "ring":
            # g consecutive disjoint edges from a random starting offset
            o = int(self.gossip_rng.integers(n))
            return [(ring[(o + 2 * k) % n], ring[(o + 2 * k + 1) % n])
                    for k in range(g)]
        # k-regular: circulant edge set over the active ring
        half = max(1, cfg.gossip_degree // 2)
        edges = [(ring[i], ring[(i + d) % n])
                 for d in range(1, half + 1) for i in range(n)
                 if ring[i] != ring[(i + d) % n]]
        pairs: List[Tuple[int, int]] = []
        used: set = set()
        for e in self.gossip_rng.permutation(len(edges)):
            i, j = edges[int(e)]
            if i not in used and j not in used:
                pairs.append((i, j))
                used.update((i, j))
                if len(pairs) == g:
                    break
        return pairs

    def _gossip_divergences(self, pairs, k_round, t):
        """Pair-incremental Algorithm-1 refresh for this tick's meetings.
        Known CLEAN pairs EMA-merge the fresh estimate (cfg.div_ema on
        the old value — two measurements of the same distributions);
        never-estimated pairs, and pairs feature drift dirtied, take it
        outright (their old value has nothing left to say)."""
        st, cfg = self.engine.state, self.engine.cfg
        parr = np.asarray(pairs, np.int32)
        pi, pj = parr[:, 0], parr[:, 1]
        ema = np.where(
            np.logical_and(st.div_known[pi, pj], ~st.div_dirty[pi, pj]),
            cfg.div_ema, 0.0)
        k_div = jax.random.fold_in(k_round, 1)
        st.div_hat = self.engine.pool.update_divergences(
            st.div_hat, st.clients, k_div, parr, ema=ema,
            **self._measure_kwargs(parr))
        st.mark_pairs_estimated(parr, t)

    def _gossip_models(self, pairs) -> Tuple[np.ndarray, int]:
        """Model exchange along solved links: inside each meeting pair,
        a target pulls its partner's model with the solved alpha weight
        (scaled by ``gossip_mix``) — the link-local, incremental
        realization of the sync engine's one-shot alpha-mixture.
        Returns (B, n_exchanges): B[s, d] holds this tick's transfer
        weights, for energy accounting.

        The updates are indexed row writes, not a dense combine: a tick
        touches at most 2*gossip_pairs rows, so mixing through the full
        (P, P) blend matrix would be O(P^2) work for O(pairs) change."""
        eng = self.engine
        st, cfg = eng.state, eng.cfg
        span = eng.trace.start("transfer")
        used = np.zeros((st.pool_size, st.pool_size))
        blends = []
        for i, j in pairs:
            for s, d in ((i, j), (j, i)):
                w = st.alpha[s, d]
                if st.psi[d] == 1.0 and w > cfg.link_thresh:
                    used[s, d] = cfg.gossip_mix * float(w)
                    if eng.faults is not None \
                            and eng.faults.drop_exchange():
                        # payload lost in flight: the sender's energy is
                        # spent (``used`` keeps the link), the receiver
                        # never applies the blend — and transmissions
                        # counts completed exchanges only
                        continue
                    blends.append((s, d, used[s, d]))
        if blends:
            # sources of solved links have psi=0 and are never blend
            # destinations, and disjoint pairs touch each destination at
            # most once — reading the pre-tick leaf is exact
            def mix(leaf):
                out = leaf
                for s, d, m in blends:
                    m = jnp.asarray(m, leaf.dtype)
                    out = out.at[d].set((1 - m) * leaf[d] + m * leaf[s])
                return out

            st.params = jax.tree_util.tree_map(mix, st.params)
        # async has no global mixture phase; the gossip exchange IS its
        # transfer, so it lands in the same trace phase/wall field
        eng.trace.stop(span, block=st.params, n_devices=st.pool_size)
        return used, len(blends)

    # --------------------------------------------------------------- tick
    def step(self, t: int) -> dict:
        eng = self.engine
        st, cfg = eng.state, eng.cfg
        t0, events, counts = self._begin(t)

        # 2. local training on the clock-eligible subset (the pool
        # decides HOW: LocalPool gathers the eligible lanes into a
        # compact batch, ShardedPool masks within each shard's block)
        elig = np.logical_and(st.active, st.clocks.eligible(t))
        k_round = jax.random.fold_in(eng.key, t)
        # measurements refresh only where a device actually ticked —
        # everyone else's view stays stale, as it would in deployment
        st.params, st.eps_hat, st.own_acc = eng.pool.train_async(
            st.params, st.clients, k_round, st.active, elig,
            st.eps_hat, st.own_acc)
        # but only devices with labeled data actually TRAIN on a tick
        # (the step's update mask); unlabeled devices progress through
        # gossip alone and must read as stale until they do
        t_idx = np.flatnonzero(np.logical_and(elig, st.labeled_devices))
        st.clocks.mark_trained(t_idx, t)

        # 3. gossip: pairwise divergence refresh + model exchange, then
        # the budgeted drift-aware re-estimation (row-targeted path)
        a = st.active_idx
        pairs = self._select_pairs(a)
        if pairs:
            self._gossip_divergences(pairs, k_round, t)
        used, n_exchanges = self._gossip_models(pairs)
        n_dirty, n_reest = self._refresh_dirty(t)

        # 4. drift + staleness gated warm re-solve
        drift = eng._drift_metric()
        reason, solve_age = self._gate(a, t, drift,
                                       patience=cfg.resolve_patience)
        resolved = reason is not None
        warm, solver_iters, solver_wall = False, 0, 0.0
        if resolved:
            warm, solver_iters, solver_wall = self._run_solve(a, t)

        # 5. evaluation + metrics (no global transfer phase: targets
        # converge to their mixtures through the gossip exchanges above)
        acc_now = np.asarray(eng.pool.accuracies(st.params, st.clients),
                             float)
        churn = self._link_churn()
        stale_dev = st.clocks.staleness(t)[a] if len(a) \
            else np.zeros(1, int)
        row, record = self._emit(
            t=t, t0=t0, a=a, acc=acc_now, events=events,
            resolved=resolved, warm=warm, solver_iters=solver_iters,
            solver_wall=solver_wall, drift=drift,
            energy=st.energy.energy(used),
            transmissions=n_exchanges, churn=churn,
            solve_age=solve_age, reason=reason,
            n_dirty_pairs=n_dirty, n_reestimated=n_reest, **counts,
            n_trained=len(t_idx), trained=[int(i) for i in t_idx],
            gossip=[[int(i), int(j)] for i, j in pairs],
            gossip_topology=cfg.gossip_topology,
            mean_staleness=float(stale_dev.mean()),
            max_staleness=float(stale_dev.max()))
        if cfg.verbose:
            print(f"[sim] tick {t}: active={len(a)} "
                  f"trained={len(t_idx)} gossip={len(pairs)} "
                  f"resolve={resolved} ({reason}) "
                  f"stale={record.mean_staleness:.1f} "
                  f"tgt_acc={record.mean_target_acc:.3f}")
        return row
