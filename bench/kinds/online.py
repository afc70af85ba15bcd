"""The ``online`` traffic kind: an autoscaler's loop.

Request ``i`` feeds ``OnlineWhatIfService`` the stream's chunk ``i``
(``observe_seconds`` of a sinusoidal-rate arrival process drawn from the
seed) and ticks.  Its check replays a sample of the ticks' grids, the
rate estimate and the threshold choice on the plain reference
(``bench/reference.py``), and the governor over every tick.
"""

from __future__ import annotations

import math

import jax
import numpy as np

import mixes
import reference
import staging


def sinusoid_chunk(stream: dict, seed: int, chunk: int, span: float) -> np.ndarray:
    """Arrival times of a sinusoidal-rate NHPP on ``[chunk*span,
    (chunk+1)*span)`` by thinning, from the seed and the chunk index alone."""
    base, amp = float(stream["base"]), float(stream["amplitude"])
    period, phase = float(stream["period"]), float(stream.get("phase", 0.0))
    lam = base * (1.0 + amp)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1, int(chunk)]))
    lo, hi = chunk * span, (chunk + 1) * span
    n = int(span * lam + 10.0 * math.sqrt(span * lam) + 32)
    cand = lo + np.cumsum(rng.exponential(1.0 / lam, size=n))
    while cand[-1] < hi:  # never at these sizes; extend rather than cut
        cand = np.concatenate([cand, cand[-1] + np.cumsum(rng.exponential(1.0 / lam, size=n))])
    cand = cand[cand < hi]
    u = rng.uniform(size=len(cand))
    rate = base * (1.0 + amp * np.sin(2.0 * np.pi * cand / period + phase))
    return cand[u * lam <= rate]


class Mix:
    """``OnlineWhatIfService``: request ``i`` observes the stream's chunk
    ``i`` (``observe_seconds`` of arrivals) and then ticks.  The service
    keeps its state from request to request, as a live one does."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import ExpSimProcess, Scenario
        from repro.serving import OnlineConfig, OnlineWhatIfService

        self.config, self.traffic = config, traffic
        svc = dict(traffic["service"])
        svc["thresholds"] = tuple(float(t) for t in svc["thresholds"])
        svc["seed"] = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
        svc["execution"] = mixes.execution(traffic["execution"])
        self.cfg = OnlineConfig(**svc)
        self.svc_seed = svc["seed"]
        self.seed = int(seed)
        self.span = float(traffic["observe_seconds"])
        self.stream = traffic["stream"]
        base = Scenario(
            arrival_process=ExpSimProcess(rate=1.0),
            warm_service_process=mixes.process(config["warm_service"]),
            cold_service_process=mixes.process(config["cold_service"]),
            expiration_threshold=float(config["expiration_threshold"]),
            max_concurrency=int(config["max_concurrency"]),
            slots=int(config["slots"]),
        )
        self.service = OnlineWhatIfService(base, self.cfg)
        self.warm = mixes.staging_dist(config["warm_service"])
        self.cold = mixes.staging_dist(config["cold_service"])
        self.horizon = float(self.cfg.sim_time if self.cfg.sim_time is not None
                             else self.cfg.n_bins * self.cfg.bin_width)
        self.steps = staging.steps_needed(self.horizon, 1.0 / float(self.cfg.rate_ceiling))
        self.chunks: dict = {}
        self.recs: list = []  # every recommendation returned, in order

    def _chunk(self, i: int) -> np.ndarray:
        if i not in self.chunks:
            self.chunks[i] = sinusoid_chunk(self.stream, self.seed, i, self.span)
        return self.chunks[i]

    def prepare(self, n: int) -> None:
        """Draw the arrivals of requests ``0..n-1`` ahead of the window:
        the stream exists before the service sees it."""
        for i in range(n):
            self._chunk(i)

    def phases(self) -> list:
        return [("bench.observe", self.observe), ("bench.tick", self.tick)]

    def observe(self, i: int) -> None:
        self.service.observe(self._chunk(i))

    def tick(self, i: int):
        rec = self.service.tick()
        if rec is not None:
            self.recs.append(rec)
        return rec

    def request(self, i: int):
        self.observe(i)
        return self.tick(i)

    def work(self, i: int) -> float:
        return 0.0

    def kernel_bytes(self, i: int) -> dict:
        return {}

    def kernel_ops(self, i: int) -> dict:
        return {}

    def ok(self, rec) -> bool:
        return rec is None or (not rec.degraded and bool(np.asarray(rec.grid.ok).all()))

    # ---- the reference's own copy of the service's estimate and choice

    def _profiles(self, n_ticks: int) -> list:
        """The swept rate profile of ticks ``0..n_ticks-1``: the stream's
        rolling window binned into ``n_bins`` rates, EMA-blended tick over
        tick and clipped to ``[rate_floor, rate_ceiling]``."""
        cfg = self.cfg
        span = cfg.n_bins * cfg.bin_width
        seen = np.empty((0,), np.float64)
        ema, out = None, []
        for i in range(n_ticks):
            seen = np.concatenate([seen, self._chunk(i)])
            now = float(seen[-1])
            t0 = max(0.0, now - span)
            rel = seen[seen >= t0] - t0
            rel = np.minimum(rel, np.nextafter(span, 0.0))
            counts, _ = np.histogram(rel, bins=cfg.n_bins, range=(0.0, span))
            rates = np.maximum(counts / cfg.bin_width, cfg.rate_floor)
            ema = rates if ema is None else cfg.ema_alpha * rates + (1 - cfg.ema_alpha) * ema
            out.append(np.clip(ema, cfg.rate_floor, cfg.rate_ceiling))
        return out

    def _select(self, cold_probs: list) -> int:
        ok = np.asarray(cold_probs) <= self.cfg.cold_slo
        return int(np.argmax(ok)) if ok.any() else len(cold_probs) - 1

    def check(self, done: list, rng, pool, control=None) -> dict:
        """Reference over ``check.ticks`` recommendations drawn from the
        seed among those returned in the window: the rate estimate, the
        swept grid, and the threshold it picks.  The governor's applied
        threshold is replayed over the raw choices of every tick.
        ``control`` as for the sweep."""
        cfg = self.cfg
        recs = [rec for _, rec in done if rec is not None]
        n_ticks = int(self.traffic["check"]["ticks"])
        picks = [recs[k] for k in mixes.pick(rng, len(recs), n_ticks)]
        profiles = self._profiles(max(r.tick for r in self.recs) + 1) if self.recs else []
        edges = tuple(float(e) for e in np.arange(1, cfg.n_bins) * cfg.bin_width)
        thresholds = list(cfg.thresholds)
        R = int(cfg.replicas)
        key = jax.random.key(self.svc_seed)
        tick_keys = []
        for _ in range(len(profiles)):
            key, sub = jax.random.split(key)
            tick_keys.append(sub)
        jobs, where = [], []
        for rec in picks:
            rates = tuple(float(r) for r in profiles[rec.tick])
            keys = staging.cell_keys(tick_keys[rec.tick], len(thresholds))
            where.append((rec, len(jobs), rates))
            for j, thr in enumerate(thresholds):
                times, warm, cold = staging.nhpp_cell(
                    keys[j], edges, rates, self.warm, self.cold, R, self.steps, self.horizon
                )
                for r in range(R):
                    jobs.append(
                        (times[r], warm[r], cold[r], thr, int(self.config["max_concurrency"]),
                         self.horizon, float(cfg.skip_time), True)
                    )
        ref_rows = mixes.run_reference(pool, reference.simulate, jobs, None)
        cand_rows = mixes.run_reference(pool, reference.simulate, jobs, control) if control else None
        out = {"decision_gap": 0.0, "integral_gap": 0.0, "choice_mismatches": 0}
        measured = self.horizon - float(cfg.skip_time)

        def grid(rows, j):
            return [
                reference.pooled(rows[j + k * R : j + (k + 1) * R], measured)
                for k in range(len(thresholds))
            ]

        for rec, j, rates in where:
            refs = grid(ref_rows, j)
            if control:
                cands = grid(cand_rows, j)
                pick = self._select([m["cold_start_prob"] for m in cands])
                chosen = cands[pick]
                cand = dict(threshold=thresholds[pick], predicted_cold_prob=chosen["cold_start_prob"],
                            predicted_avg_replicas=chosen["avg_server_count"],
                            predicted_goodput=chosen["goodput"], rate_mean=_mean_rate(rates))
            else:
                cands = [
                    {f: np.asarray(getattr(rec.grid, f))[k]
                     for f in mixes.DECISION + mixes.INTEGRAL}
                    for k in range(len(thresholds))
                ]
                cand = dict(threshold=rec.threshold, predicted_cold_prob=rec.predicted_cold_prob,
                            predicted_avg_replicas=rec.predicted_avg_replicas,
                            predicted_goodput=rec.predicted_goodput, rate_mean=rec.rate_mean)
            for c_m, r_m in zip(cands, refs):
                mixes.fold_gaps(c_m, r_m, out)
            pick = self._select([m["cold_start_prob"] for m in refs])
            out["choice_mismatches"] += int(thresholds[pick] != cand["threshold"])
            chosen = refs[pick]
            for name, ref in (
                ("predicted_cold_prob", chosen["cold_start_prob"]),
                ("predicted_avg_replicas", chosen["avg_server_count"]),
                ("predicted_goodput", chosen["goodput"]),
                ("rate_mean", _mean_rate(rates)),
            ):
                out["integral_gap"] = max(out["integral_gap"], reference.relative_gap(cand[name], ref))
        if not control:
            out["choice_mismatches"] += self._governor_mismatches()
        out["ticks_checked"] = len(picks)
        return out

    def _governor_mismatches(self) -> int:
        """Applied thresholds that differ from the hysteresis rule (a
        proposal applies once it repeats ``patience`` times outside the
        ``deadband``) replayed over the raw choices in order."""
        applied, cand, streak, bad = None, None, 0, 0
        for rec in self.recs:
            p = float(rec.threshold)
            if applied is None:
                applied = p
            elif abs(p - applied) <= self.cfg.deadband * abs(applied):
                cand, streak = None, 0
            else:
                if p == cand:
                    streak += 1
                else:
                    cand, streak = p, 1
                if streak >= self.cfg.patience:
                    applied, cand, streak = cand, None, 0
            bad += int(applied != rec.applied_threshold)
        return bad


def _mean_rate(rates) -> float:
    """Time-averaged rate of uniform bins."""
    return float(np.mean(np.asarray(rates, np.float64)))
