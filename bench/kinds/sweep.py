"""The ``sweep`` traffic kind: a planner's what-if grid.

Request ``i`` is one ``scenario.sweep`` of the configuration's function
over a product grid of keep-alive thresholds and arrival rates, with
``replicas`` rows per grid cell, keyed by ``fold_in(key, i)``.  Its check
replays a sample of grid cells on the plain reference
(``bench/reference.py``) with the draws the timed path consumed.
"""

from __future__ import annotations

import jax
import numpy as np

import mixes
import reference
import staging

F32 = 4  # bytes of one float32
# vector operations per instance slot and event that the event step needs:
# expiry test and select, the running and idle integrals (min, max,
# subtract, test, add each), the idle test and the newest-idle pick
OPS_PER_SLOT = 12


class Mix:
    """``scenario.sweep`` of one function over a product grid of keep-alive
    thresholds and arrival rates, ``replicas`` rows per grid cell."""

    kernel = "faas_kernel"
    simulate = staticmethod(reference.simulate)  # the plain reference of one replica row

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import Scenario

        self.config, self.traffic = config, traffic
        self.over = {n: mixes.axis_values(v) for n, v in traffic["axes"].items()}
        if list(self.over) != ["expiration_threshold", "arrival_rate"]:
            raise ValueError("a sweep traffic file sweeps expiration_threshold, then arrival_rate")
        self.replicas = int(traffic["replicas"])
        self.sim_time = float(traffic["sim_time"])
        self.skip_time = float(config["skip_time"])
        self.steps = staging.steps_needed(self.sim_time, 1.0 / float(traffic["steps_rate"]))
        self.execution = mixes.execution(traffic["execution"])
        self.warm = mixes.staging_dist(config["warm_service"])
        self.cold = mixes.staging_dist(config["cold_service"])
        self.scenario = Scenario(
            arrival_process=mixes.process(config["arrival"]),
            warm_service_process=mixes.process(config["warm_service"]),
            cold_service_process=mixes.process(config["cold_service"]),
            expiration_threshold=float(config["expiration_threshold"]),
            max_concurrency=int(config["max_concurrency"]),
            slots=int(config["slots"]),
            sim_time=self.sim_time,
            skip_time=self.skip_time,
        )
        self.key = mixes.base_key(seed)
        thr, rates = self.over["expiration_threshold"], self.over["arrival_rate"]
        self.cells = [(t, r) for t in thr for r in rates]  # draw-cell order
        self.rows = len(self.cells) * self.replicas
        self._work = self.replicas * self.sim_time * sum(r for _, r in self.cells)

    def request(self, i: int):
        from repro.core import scenario

        return scenario.sweep(
            self.scenario,
            over=self.over,
            key=jax.random.fold_in(self.key, i),
            replicas=self.replicas,
            steps=self.steps,
            execution=self.execution,
        )

    def prepare(self, n: int) -> None:
        """Nothing to draw ahead: a request is its key."""

    def phases(self) -> list:
        return [("bench.sweep", self.request)]

    def work(self, i: int) -> float:
        return self._work

    def kernel_bytes(self, i: int) -> dict:
        """Three ``[rows, steps]`` float32 draw stacks read once, and per
        row 3 inputs (threshold, horizon, warm-up) and 8 accumulators out."""
        return {self.kernel: self.rows * (3 * self.steps + 3 + 8) * F32}

    def kernel_ops(self, i: int) -> dict:
        """Per row and event, ``OPS_PER_SLOT`` vector operations on each of
        the pool's ``slots`` instances."""
        return {self.kernel: self.rows * self.steps * int(self.config["slots"]) * OPS_PER_SLOT}

    def ok(self, result) -> bool:
        return bool(np.asarray(result.ok).all())

    def check(self, done: list, rng, pool, control=None) -> dict:
        """Reference over ``check.cells`` grid cells drawn from the seed
        among every cell of every finished request, all replicas each.
        With ``control`` (a precision) the reference run at that precision
        stands in the program's place."""
        n_cells = len(self.cells)
        picks = mixes.pick(rng, len(done) * n_cells, int(self.traffic["check"]["cells"]))
        jobs, units = [], []
        n_rate = len(self.over["arrival_rate"])
        measured = self.sim_time - self.skip_time
        for p in picks:
            i, result = done[p // n_cells]
            c = p % n_cells
            thr, rate = self.cells[c]
            key = staging.cell_keys(jax.random.fold_in(self.key, i), c + 1)[c]
            arr, warm, cold = staging.single_cell(
                key, staging.rate_dist(rate), self.warm, self.cold, self.replicas, self.steps
            )
            j = len(jobs)
            for r in range(self.replicas):
                jobs.append(
                    (arr[r], warm[r], cold[r], thr, int(self.config["max_concurrency"]),
                     self.sim_time, self.skip_time, False)
                )
            idx = divmod(c, n_rate)
            units.append((
                lambda rows, j=j: reference.pooled(rows[j : j + self.replicas], measured),
                lambda result=result, idx=idx: {
                    f: np.asarray(getattr(result, f))[idx] for f in mixes.DECISION + mixes.INTEGRAL
                },
            ))
        out = mixes.compare(pool, self.simulate, jobs, units, control)
        out["cells_checked"] = len(picks)
        return out
