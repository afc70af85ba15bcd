"""The benchmark's one traffic generator: one class per traffic kind.

A traffic file (``bench/traffic/<mix>.json``) names a ``kind`` and its
parameters; a configuration file (``bench/configs/<config>.json``) names
the deployment.  ``build`` turns the pair and a seed into a mix whose

* ``request(i)`` sends request ``i`` to the program's public entry point
  and returns its result (the window's unit of work);
* ``work(i)`` is what request ``i`` asked to simulate, in arrivals:
  the sum over its grid rows of arrival rate times horizon, worked out
  from the request and never from the result;
* ``kernel_bytes(i)`` the HBM bytes the staged-draw semantics requires a
  request's kernel to move (the roofline's numerator);
* ``check(done, rng, pool, control)`` compares a sample of the finished requests
  with the plain reference (``bench/reference.py``) on the draws the
  timed path consumed (``bench/staging.py``) and returns the compared
  numbers.

Kinds: ``sweep`` (``scenario.sweep`` over a product grid) and ``online``
(``OnlineWhatIfService``: observe a span of a live stream, then tick).
Every size, rate and option a request uses is read from the two files,
so a new cell needs new data files and no new code.
"""

from __future__ import annotations

import math

import jax
import numpy as np

import reference
import staging

F32 = 4  # bytes of one float32
# vector operations per instance slot and event that the event step needs:
# expiry test and select, the running and idle integrals (min, max,
# subtract, test, add each), the idle test and the newest-idle pick
OPS_PER_SLOT = 12


def base_key(seed: int):
    """A JAX key from any whole-number seed (more than 32 bits welcome)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def axis_values(spec) -> list:
    """An axis of a traffic file: a list, or ``{"linspace": [a, b, n]}``."""
    if isinstance(spec, dict):
        a, b, n = spec["linspace"]
        return [float(x) for x in np.linspace(a, b, int(n))]
    return [float(x) for x in spec]


def _process(dist: dict):
    from repro.core import ExpSimProcess, GaussianSimProcess, LogNormalSimProcess

    if dist["dist"] == "exp":
        return ExpSimProcess(rate=staging_dist(dist)["rate"])
    if dist["dist"] == "gauss":
        return GaussianSimProcess(mu=dist["mu"], sigma=dist["sigma"])
    if dist["dist"] == "lognorm":
        return LogNormalSimProcess(mu=dist["mu"], sigma=dist["sigma"])
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def staging_dist(dist: dict) -> dict:
    """A configuration's distribution entry as ``staging.sample`` takes it
    (an exponential given by its mean becomes the rate ``1 / mean``)."""
    if dist["dist"] == "exp" and "mean" in dist:
        return staging.exp_mean(dist["mean"])
    return dist


def _execution(spec: dict):
    from repro.core import Execution

    return Execution(**spec)


def _pick(rng, n_items: int, k: int) -> list:
    return sorted(rng.choice(n_items, size=min(k, n_items), replace=False).tolist())


def _gaps(prog: dict, ref: dict, out: dict) -> None:
    """Fold one cell's gaps into the running maxima ``out``.  A probability
    is compared by its absolute gap: a function invoked a few hundred
    times has a handful of cold starts, whose relative gap one decision
    flipped near a tie would swing by tens of per cent."""
    for f in DECISION:
        if f.endswith("_prob"):
            gap = abs(float(prog[f]) - float(ref[f]))
            gap = gap if math.isfinite(gap) else math.inf
        else:
            gap = reference.relative_gap(prog[f], ref[f])
        out["decision_gap"] = max(out["decision_gap"], gap)
    for f in INTEGRAL:
        out["integral_gap"] = max(out["integral_gap"], reference.relative_gap(prog[f], ref[f]))


def _compare(pool, fn, jobs, units, control) -> dict:
    """Run the reference over ``jobs`` and fold each unit's gaps.

    A unit is ``(ref_of, prog_of)``: ``ref_of(rows)`` reads the unit's
    metrics off reference rows, ``prog_of()``
    off the program's result.  With ``control`` the reference run at that
    precision takes the program's place."""
    ref_rows = run_reference(pool, fn, jobs, None)
    cand_rows = run_reference(pool, fn, jobs, control) if control else None
    out = {"decision_gap": 0.0, "integral_gap": 0.0}
    for ref_of, prog_of in units:
        _gaps(ref_of(cand_rows) if control else prog_of(), ref_of(ref_rows), out)
    return out


DECISION = ("cold_start_prob", "rejection_prob")
INTEGRAL = (
    "avg_server_count",
    "avg_running_count",
    "avg_idle_count",
    "wasted_ratio",
    "avg_response_time",
)


class SweepMix:
    """``scenario.sweep`` of one function over a product grid of keep-alive
    thresholds and arrival rates, ``replicas`` rows per grid cell."""

    kernel = "faas_kernel"

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import Scenario

        self.config, self.traffic = config, traffic
        self.over = {n: axis_values(v) for n, v in traffic["axes"].items()}
        if list(self.over) != ["expiration_threshold", "arrival_rate"]:
            raise ValueError("a sweep traffic file sweeps expiration_threshold, then arrival_rate")
        self.replicas = int(traffic["replicas"])
        self.sim_time = float(traffic["sim_time"])
        self.skip_time = float(config["skip_time"])
        self.steps = staging.steps_needed(self.sim_time, 1.0 / float(traffic["steps_rate"]))
        self.execution = _execution(traffic["execution"])
        self.warm = staging_dist(config["warm_service"])
        self.cold = staging_dist(config["cold_service"])
        self.scenario = Scenario(
            arrival_process=_process(config["arrival"]),
            warm_service_process=_process(config["warm_service"]),
            cold_service_process=_process(config["cold_service"]),
            expiration_threshold=float(config["expiration_threshold"]),
            max_concurrency=int(config["max_concurrency"]),
            slots=int(config["slots"]),
            sim_time=self.sim_time,
            skip_time=self.skip_time,
        )
        self.key = base_key(seed)
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
        picks = _pick(rng, len(done) * n_cells, int(self.traffic["check"]["cells"]))
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
                    f: np.asarray(getattr(result, f))[idx] for f in DECISION + INTEGRAL
                },
            ))
        out = _compare(pool, reference.simulate, jobs, units, control)
        out["cells_checked"] = len(picks)
        return out


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


class OnlineMix:
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
        svc["execution"] = _execution(traffic["execution"])
        self.cfg = OnlineConfig(**svc)
        self.svc_seed = svc["seed"]
        self.seed = int(seed)
        self.span = float(traffic["observe_seconds"])
        self.stream = traffic["stream"]
        base = Scenario(
            arrival_process=ExpSimProcess(rate=1.0),
            warm_service_process=_process(config["warm_service"]),
            cold_service_process=_process(config["cold_service"]),
            expiration_threshold=float(config["expiration_threshold"]),
            max_concurrency=int(config["max_concurrency"]),
            slots=int(config["slots"]),
        )
        self.service = OnlineWhatIfService(base, self.cfg)
        self.warm = staging_dist(config["warm_service"])
        self.cold = staging_dist(config["cold_service"])
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
        picks = [recs[k] for k in _pick(rng, len(recs), int(self.traffic["check"]["ticks"]))]
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
        ref_rows = run_reference(pool, reference.simulate, jobs, None)
        cand_rows = run_reference(pool, reference.simulate, jobs, control) if control else None
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
                    {f: np.asarray(getattr(rec.grid, f))[k] for f in DECISION + INTEGRAL}
                    for k in range(len(thresholds))
                ]
                cand = dict(threshold=rec.threshold, predicted_cold_prob=rec.predicted_cold_prob,
                            predicted_avg_replicas=rec.predicted_avg_replicas,
                            predicted_goodput=rec.predicted_goodput, rate_mean=rec.rate_mean)
            for c_m, r_m in zip(cands, refs):
                _gaps(c_m, r_m, out)
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


def run_reference(pool, fn, jobs: list, precision) -> list:
    """Run the reference over ``jobs`` (argument tuples), in ``pool`` when
    one is given; ``precision`` (a control's) rounds every time it
    computes."""
    args = [(fn, job, precision) for job in jobs]
    if pool is None:
        return [reference.call(a) for a in args]
    return pool.map(reference.call, args, chunksize=1)


KINDS = {"sweep": SweepMix, "online": OnlineMix}


def build(config: dict, traffic: dict, seed: int):
    try:
        kind = KINDS[traffic["kind"]]
    except KeyError:
        raise ValueError(f"unknown traffic kind {traffic.get('kind')!r}; kinds: {sorted(KINDS)}") from None
    return kind(config, traffic, seed)


LOWER = {"float64": "float32", "float32": "bfloat16"}


def control_precision(config: dict, traffic: dict) -> str:
    """The control's precision: the next below the one the configuration
    states for the traffic's backend."""
    backend = traffic["execution"].get("backend", "scan")
    return LOWER[config["precision"][backend]]
