"""What the warm-up rounds did, kept for the correctness check.

The warm-up drives ``SimulationEngine.step`` on the engine the window
then uses: the same object, the same compiled programs, the same shapes.
While it runs, ``Capture`` wraps the engine's device pool and keeps, per
round, the inputs that the plain reference needs to follow the same
rounds from the seed (the devices' data, the active set, the pairs
measured, the solved assignment the transfer used) and the
answers the program gave (accuracies, and at the end the parameter
stack, flat by path as ``replay.flat_leaves`` keys it, and the
divergence matrix).  The wrappers come off before the
window starts.
"""
from __future__ import annotations

from typing import List

import numpy as np

from harness.replay import flat_leaves

POOL_CALLS = ("train", "update_divergences", "refresh_divergences",
              "transfer", "accuracies")


class Capture:
    def __init__(self, engine):
        self.engine = engine
        self.ticks: List[dict] = []
        self._cur: dict = {}
        pool = engine.pool
        for name in POOL_CALLS:
            setattr(pool, name, self._wrap(name, getattr(pool, name)))

    def _wrap(self, name, fn):
        def call(*args, **kwargs):
            out = fn(*args, **kwargs)
            self._record(name, args, out)
            return out
        return call

    def _record(self, name, args, out):
        cur, st = self._cur, self.engine.state
        if name == "train":
            # the data the round trains on, after the scenario's drift
            cur["data"] = list(st.pool)
            cur["active"] = np.asarray(args[3], bool).copy()
        elif name in ("update_divergences", "refresh_divergences"):
            cur.setdefault("div_calls", []).append(
                ("refresh" if name == "refresh_divergences" else "update",
                 np.asarray(args[3], np.int32).reshape(-1, 2).copy()))
        elif name == "transfer":
            cur["transfer"] = (np.array(args[1], float),
                               np.array(args[2], float))
        elif name == "accuracies":
            cur["acc"] = np.asarray(out, float).copy()

    def step(self, t: int) -> dict:
        st = self.engine.state
        self._cur = {"tick": t}
        row = self.engine.step(t)
        st.round = t + 1
        self._cur.update(row=row, alpha=np.array(st.alpha),
                         psi=np.array(st.psi))
        self.ticks.append(self._cur)
        return row

    def finish(self) -> dict:
        """Take the wrappers off and keep the program's end state."""
        pool = self.engine.pool
        for name in POOL_CALLS:
            del pool.__dict__[name]
        st = self.engine.state
        return {"params": flat_leaves(st.params),
                "div_hat": np.array(st.div_hat)}
