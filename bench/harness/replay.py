"""The correctness check: the plain reference follows the warm-up rounds
from the seed, and its answers are compared with the program's.

The reference is the client's reference file, reached only through the
contract in ``spec.py``: its ``init`` gives the per-device parameters,
its ``stack`` the device-major data layout, and the class count is the
configuration's ``model.classes``.  It starts from its own weights,
drawn from the seed with the simulator's key schedule, and follows each
warm-up round as the sync executor defines it: every labeled active
device trains, then targets take the alpha-mixture of the assignment
the round installed.  It measures the accuracy of every device after
each round.  Pair divergences (Algorithm 1) are followed for a sample of
the measured pairs, drawn from the seed, through every measurement each
had in the warm-up (the bootstrap, the drift refresh), with the
simulator's merge rule.

What the reference takes from the run is its input and its control
decisions: each device's data (the traffic), which devices were active,
which pairs were measured or refreshed, and the solved assignment (psi,
alpha).  The solver has no reference here; its assignment is held to
the constraints of program (P) instead (``solve_faults``).

Numbers read (each a worst case over the warm-up; the cell's limits
file says which are compared):
  param_gap     per leaf of the device stack (keyed by its path in the
                parameter tree, ``flat_leaves``), the gap between the
                norms of the parameter change over the warm-up in the
                program and in the reference, over the larger of the
                reference's change of that leaf and of the median leaf;
                the worst leaf
  acc_gap       the largest |accuracy_prog - accuracy_ref| of a device
                in any warm-up round
  acc_gap_mean  the mean |accuracy_prog - accuracy_ref| over the
                active devices and warm-up rounds
  div_gap       the largest |d_prog - d_ref| of a sampled pair
  div_gap_mean  the mean |d_prog - d_ref| over the sampled pairs
  solve_faults  assignments that break (P)'s constraints, and rounds
                whose logged targets or links differ from the
                assignment; limit 0
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: content-addressed refresh streams: fold_in(PRNGKey(seed), ...) of the
#: pair keys and of the shared classifier init
CONTENT_KEY_STREAM = 2 ** 20
CONTENT_INIT_STREAM = 2 ** 21
#: the simulator's pair-key chunk (keys of pair p come from chunk p // 256)
PAIR_KEY_CHUNK = 256


#: Algorithm 1's classifier tells device i's rows from device j's
DOMAIN_CLASSES = 2


def flat_leaves(tree) -> Dict[str, np.ndarray]:
    """The leaves of a parameter tree as numpy arrays, keyed by their
    path: a flat dict keeps its names (``conv1``), a nested one joins
    them (``adapter/down``)."""
    return {jax.tree_util.keystr(path, simple=True, separator="/"):
            np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _root_keys(seed: int):
    k0 = jax.random.PRNGKey(seed)
    _, run_key = jax.random.split(k0)      # (init key, run key)
    return k0, run_key


def _positional_key(key, npairs: int, p: int):
    if npairs <= PAIR_KEY_CHUNK:
        return jax.random.split(key, npairs)[p]
    c0 = (p // PAIR_KEY_CHUNK) * PAIR_KEY_CHUNK
    return jax.random.split(jax.random.fold_in(key, c0),
                            PAIR_KEY_CHUNK)[p - c0]


def sample_pairs(ticks, seed: int, per_call: int) -> List[Tuple[int, int]]:
    """Up to ``per_call`` distinct pairs of each measurement call of the
    warm-up, drawn from the seed."""
    rng = np.random.default_rng([seed, 17])
    chosen: Dict[Tuple[int, int], None] = {}
    for tick in ticks:
        for _, pairs in tick.get("div_calls", []):
            canon = [(int(min(a, b)), int(max(a, b))) for a, b in pairs]
            pick = rng.choice(len(canon), size=min(per_call, len(canon)),
                              replace=False)
            for k in sorted(pick):
                chosen[canon[k]] = None
    return list(chosen)


def _measurements(ticks, pairs, div_ema: float, key_mode: str):
    """Every measurement of each sampled pair, in order:
    (pair, tick, kind, position in its call, call size, ema weight)."""
    want = set(pairs)
    known = set()
    last = {}                      # pair -> tick of its last measurement
    drifted: Dict[int, List[int]] = {}
    out = []
    for tick in ticks:
        t = tick["tick"]
        for ev in tick["row"].get("events", []):
            if ev.get("event") == "feature_drift":
                drifted.setdefault(int(ev["device"]), []).append(t)
        for kind, arr in tick.get("div_calls", []):
            for pos, (a, b) in enumerate(arr):
                pair = (int(min(a, b)), int(max(a, b)))
                dirty = any(last.get(pair, -1) < u <= t
                            for d in pair for u in drifted.get(d, []))
                ema = div_ema if (kind == "refresh" and pair in known
                                  and not dirty) else 0.0
                mode = "content" if (kind == "refresh"
                                     or key_mode == "content") \
                    else "positional"
                if pair in want:
                    out.append((pair, t, mode, pos, len(arr), ema))
                known.add(pair)
                last[pair] = t
    return out


class Follower:
    """The reference's run of the warm-up rounds, in ``dtype``, of a
    client with ``classes`` classes."""

    def __init__(self, ref, cell_sim: dict, seed: int, dtype, classes: int):
        self.ref = ref
        self.sim = cell_sim
        self.seed = seed
        self.dtype = dtype
        self.classes = classes

    # ------------------------------------------------------ devices
    def devices(self, ticks) -> Tuple[dict, List[np.ndarray], dict]:
        """Parameters after the last warm-up round, every device's
        accuracy after each round, and the parameters at the start; the
        parameters flat by path (``flat_leaves``)."""
        sim, ref, dt = self.sim, self.ref, self.dtype
        k0, run_key = _root_keys(self.seed)
        k_init = jax.random.split(k0)[0]
        n = len(ticks[0]["data"])
        p0 = ref.init(k_init, self.classes)
        params = ref.cast(jax.tree_util.tree_map(
            lambda v: jnp.broadcast_to(v, (n,) + v.shape), p0), dt)
        accs = []
        data, data_src = None, None
        for tick in ticks:
            t = tick["tick"]
            if data_src is None or any(
                    a is not b for a, b in zip(data_src, tick["data"])):
                data_src = tick["data"]
                data = ref.stack(data_src, dt)
            update = np.asarray(data["labeled"]).any(axis=1) & \
                tick["active"]
            lane_keys = jax.random.split(jax.random.fold_in(run_key, t), n)
            params = ref.train(params, data["x"], data["y"],
                               data["labeled"], data["valid"], lane_keys,
                               jnp.asarray(update), iters=sim["train_iters"],
                               batch=sim["batch"], lr=sim["lr"], dtype=dt)
            alpha, psi = tick["transfer"]
            params = ref.mix_targets(params, alpha, psi, dt)
            accs.append(np.asarray(ref.accuracies(
                params, data["x"], data["true_y"], data["valid"],
                dtype=dt), float))
        init = {k: np.broadcast_to(v, (n,) + v.shape)
                for k, v in flat_leaves(p0).items()}
        return ({k: np.asarray(v, np.float32)
                 for k, v in flat_leaves(params).items()}, accs, init)

    # --------------------------------------------------------- pairs
    def pairs(self, ticks, pairs) -> Dict[Tuple[int, int], float]:
        """The reference's final estimate of each sampled pair."""
        sim, ref, dt = self.sim, self.ref, self.dtype
        meas = _measurements(ticks, pairs, sim["div_ema"],
                             sim.get("div_key_mode", "positional"))
        if not meas:
            return {}
        k0, run_key = _root_keys(self.seed)
        content_h0 = ref.init(jax.random.fold_in(k0, CONTENT_INIT_STREAM),
                              DOMAIN_CLASSES)
        content_base = jax.random.fold_in(k0, CONTENT_KEY_STREAM)
        by_tick = {tick["tick"]: tick for tick in ticks}
        devs_i, devs_j, ns_i, ns_j, keys, h0s = [], [], [], [], [], []
        for (a, b), t, mode, pos, size, _ in meas:
            data = by_tick[t]["data"]
            devs_i.append(data[a])
            devs_j.append(data[b])
            ns_i.append(len(data[a].labels))
            ns_j.append(len(data[b].labels))
            if mode == "content":
                keys.append(jax.random.fold_in(
                    jax.random.fold_in(content_base, a), b))
                h0s.append(content_h0)
            else:
                k_div = jax.random.fold_in(jax.random.fold_in(run_key, t),
                                           1)
                key, init_key = jax.random.split(k_div)
                keys.append(_positional_key(key, size, pos))
                h0s.append(ref.init(init_key, DOMAIN_CLASSES))
        # both sides padded to one length: the reference's draws over
        # padded rows and its compiled widths depend on it
        n_max = max(ns_i + ns_j)
        # lanes padded to a power of two by repeating the first, so the
        # reference compiles for a few widths, not for every count
        lanes = max(64, 1 << (len(meas) - 1).bit_length())
        for lst in (devs_i, devs_j, ns_i, ns_j, keys, h0s):
            lst.extend(lst[:1] * (lanes - len(meas)))
        h0 = ref.cast(jax.tree_util.tree_map(lambda *v: jnp.stack(v),
                                             *h0s), dt)
        vals = np.asarray(ref.pair_divergence(
            h0, ref.stack(devs_i, dt, n_max)["x"], jnp.asarray(ns_i),
            ref.stack(devs_j, dt, n_max)["x"], jnp.asarray(ns_j),
            jnp.stack(keys), tau=sim["div_tau"], T=sim["div_T"],
            batch=sim["batch"], lr=sim["lr"], dtype=dt), float)[:len(meas)]
        est: Dict[Tuple[int, int], float] = {}
        for (pair, _, _, _, _, ema), v in zip(meas, vals):
            est[pair] = ema * est.get(pair, 0.0) + (1.0 - ema) * v
        return est


# ----------------------------------------------------------- compare
def param_gap(prog: dict, ref: dict, init: dict) -> float:
    """Per leaf of the device stack, the gap between the norms of the
    program's and the reference's parameter change over the warm-up (not
    the norm of their difference: the directions part under rounding),
    over the larger of the reference's norm of that leaf and of the
    median leaf; the worst leaf.  Leaves are matched by their path."""
    prog, ref, init = flat_leaves(prog), flat_leaves(ref), flat_leaves(init)

    def change(params, name):
        return float(np.linalg.norm(np.asarray(params[name], np.float64)
                                    - np.asarray(init[name], np.float64)))

    ch_ref = {name: change(ref, name) for name in ref}
    floor = max(float(np.median(list(ch_ref.values()))), 1e-30)
    return max(abs(change(prog, name) - ch_ref[name])
               / max(ch_ref[name], floor) for name in ref)


def acc_gap(prog: List[np.ndarray], ref: List[np.ndarray],
            active: List[np.ndarray]) -> float:
    worst = 0.0
    for p, r, a in zip(prog, ref, active):
        if a.any():
            worst = max(worst, float(np.max(np.abs(p[a] - r[a]))))
    return worst


def acc_gap_mean(prog: List[np.ndarray], ref: List[np.ndarray],
                 active: List[np.ndarray]) -> float:
    gaps = [np.abs(p[a] - r[a]) for p, r, a in zip(prog, ref, active)]
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    return float(gaps.mean()) if gaps.size else 0.0


def div_gap(prog: Dict[Tuple[int, int], float],
            ref: Dict[Tuple[int, int], float]) -> float:
    return float(max((abs(prog[k] - v) for k, v in ref.items()),
                     default=0.0))


def div_gap_mean(prog: Dict[Tuple[int, int], float],
                 ref: Dict[Tuple[int, int], float]) -> float:
    return float(np.mean([abs(prog[k] - v) for k, v in ref.items()])) \
        if ref else 0.0


def solve_faults(ticks, link_thresh: float, tol: float = 1e-6) -> int:
    """Count assignments that break (P)'s constraints, and rounds whose
    logged targets or links are not the installed assignment's."""
    faults = 0
    for tick in ticks:
        psi, alpha, row = tick["psi"], tick["alpha"], tick["row"]
        act = tick["active"]
        src = act & (psi == 0.0)
        tgt = act & (psi == 1.0)
        ok = bool(np.all((psi == 0.0) | (psi == 1.0)))
        ok &= bool(np.all(psi[~act] == 0.0))
        ok &= bool(np.all(alpha >= 0.0))
        allowed = np.outer(src, tgt)
        np.fill_diagonal(allowed, False)
        ok &= bool(np.all(alpha[~allowed] == 0.0))
        if tgt.any():
            ok &= bool(np.all(np.abs(alpha[:, tgt].sum(axis=0) - 1.0)
                              <= tol))
        faults += not ok
        links = sorted([int(s), int(d)] for s, d in
                       zip(*np.nonzero(alpha > link_thresh)))
        faults += row["targets"] != [int(j) for j in np.flatnonzero(tgt)]
        faults += row["links"] != links
    return int(faults)


def follow(follower: Follower, ticks, pairs) -> dict:
    params, accs, init = follower.devices(ticks)
    return {"params": params, "accs": accs, "init": init,
            "div": follower.pairs(ticks, pairs)}


def program_side(ticks, end: dict, pairs) -> dict:
    """The program's answers, in the follower's layout."""
    return {"params": end["params"], "accs": [t["acc"] for t in ticks],
            "div": {(a, b): float(end["div_hat"][a, b]) for a, b in pairs}}


def compare(side: dict, ref: dict, ticks) -> dict:
    """The numbers compared of ``side`` (the program, or the control in
    its place) against the reference."""
    return {
        "param_gap": param_gap(side["params"], ref["params"], ref["init"]),
        "acc_gap": acc_gap(side["accs"], ref["accs"],
                           [t["active"] for t in ticks]),
        "acc_gap_mean": acc_gap_mean(side["accs"], ref["accs"],
                                     [t["active"] for t in ticks]),
        "div_gap": div_gap(side["div"], ref["div"]),
        "div_gap_mean": div_gap_mean(side["div"], ref["div"]),
    }
