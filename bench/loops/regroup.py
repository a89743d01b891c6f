"""Phase-1 regrouping: one call is one ``choose_k`` over a fresh draw of
the fleet's node profiles, with the grouping's own defaults, as phase 1
calls it.

The check groups a sample of the window's inputs, drawn from the seed and
with the slowest call among them, with the plain reference on the host's
CPU device, and compares the chosen k and the labels.
"""
from __future__ import annotations

import numpy as np

from bench import gen

WINDOW_STREAM, CHECK_STREAM = 1, 2
WARM_SEED = 20211105           # set-up's draw: the same in every run


class Loop:
    span = "choose_k"

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.seed = seed
        self.traffic = traffic
        self.profiles = cfg["profiles"]
        self.n = self.profiles["n"]
        self.calls: list[dict] = []

    def _inputs(self, seed: int) -> np.ndarray:
        return gen.fleet_profiles(self.n, seed, self.profiles)

    def _choose_k(self, X):
        from repro.core import clustering
        return clustering.choose_k(X, k_max=self.traffic["k_max"],
                                   restarts=self.traffic["restarts"])

    def setup(self):
        self._choose_k(self._inputs(WARM_SEED))

    def call(self, i: int) -> dict:
        seed = gen.derive_seed(self.seed, WINDOW_STREAM, i)
        X = self._inputs(seed)
        rec = {"seed": seed}
        self.calls.append(rec)
        res = self._choose_k(X)
        rec.update(k=int(res["k"]), labels=np.asarray(res["labels"], np.int8))
        return rec

    def sample(self) -> list[int]:
        """Calls to check: a seeded sample of the completed ones, with the
        slowest among them."""
        done = [i for i, c in enumerate(self.calls) if "k" in c]
        if not done:
            return []
        rng = np.random.default_rng(gen.derive_seed(self.seed, CHECK_STREAM))
        n = min(self.traffic["check_calls"], len(done))
        picked = [done[j] for j in rng.choice(len(done), n, replace=False)]
        slowest = max(done, key=lambda i: self.calls[i].get("latency_s", 0.0))
        if slowest not in picked:
            picked[-1] = slowest
        return sorted(picked)

    def check(self, control: bool = False) -> dict:
        import jax
        import jax.numpy as jnp

        from bench import compare
        from bench.reference import kmeans as ref

        kw = dict(k_max=self.traffic["k_max"],
                  restarts=self.traffic["restarts"])
        pairs = []
        with jax.default_device(jax.devices("cpu")[0]):
            for i in self.sample():
                X = self._inputs(self.calls[i]["seed"])
                r = ref.choose_k(X, **kw)
                p = ref.choose_k(X, dtype=jnp.bfloat16, **kw) if control \
                    else self.calls[i]
                pairs.append((p, r))
        self.checked = len(pairs)
        return compare.grouping_numbers(pairs)

    def release(self):
        pass
