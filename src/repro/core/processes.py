"""SimProcess: pluggable stochastic processes for SimFaaS.

The paper's ``SimProcess`` class abstracts the arrival, warm-service and
cold-service processes so that the simulator is not limited to Markovian
assumptions.  Here a process is a small frozen dataclass with a vectorised
``sample(key, shape)`` drawing a whole tensor of i.i.d. samples at once —
samples are pre-drawn outside the scan, which is both faster on SIMD
hardware and makes seed-exact cross-validation against the pure-Python
reference trivial (both consume the same sample arrays).

Shipping distributions mirror (and extend) the paper's examples:
exponential, (truncated) Gaussian, deterministic — plus Weibull, Gamma,
LogNormal, Pareto and a batch-arrival wrapper, demonstrating the
beyond-Markovian claim.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_EPS = 1e-9  # service/inter-arrival times are clamped strictly positive


def _exp_draw(key, shape, rate, _=None):
    return jax.random.exponential(key, shape) / rate


def _gauss_draw(key, shape, mu, sigma):
    return mu + sigma * jax.random.normal(key, shape)


def _lognorm_draw(key, shape, mu, sigma):
    return jnp.exp(mu + sigma * jax.random.normal(key, shape))


# Raw draws by ``draw_spec`` kind, the distribution's two parameters passed
# as values (Python floats, or traced arrays): the processes' own samplers
# and the batched staging program (``simulator.stage_draw_cells``) both
# call these, so both draw bitwise the same numbers from the same key.
RAW_DRAWS = {"exp": _exp_draw, "gauss": _gauss_draw, "lognorm": _lognorm_draw}


def finish_sample(raw) -> Array:
    """A raw draw as the engines take it: f32, clamped strictly positive."""
    return jnp.maximum(raw.astype(jnp.float32), _EPS)


def absolute_times_from_gaps(gaps) -> Array:
    """f64 absolute timestamps from an inter-arrival gap stream.

    One cumulative sum along the last axis — the reliability layer uses
    this to anchor retry attempts on a shared absolute clock, so the f64
    scan, the f32 block kernels and the pure-Python oracle all consume the
    identical pre-built event table (the f32 cast happens *after* the
    table is sorted).
    """
    return jnp.cumsum(jnp.asarray(gaps, jnp.float64), axis=-1)


@dataclasses.dataclass(frozen=True)
class SimProcess:
    """Base class.  Subclasses implement ``_raw_sample`` and ``mean``."""

    def sample(self, key: Array, shape: tuple[int, ...]) -> Array:
        """Draw ``shape`` i.i.d. samples (f32, strictly positive)."""
        return finish_sample(self._raw_sample(key, shape))

    def _raw_sample(self, key: Array, shape: tuple[int, ...]) -> Array:
        raise NotImplementedError

    def mean(self) -> float:
        raise NotImplementedError

    def with_rate(self, rate: float) -> "SimProcess":
        """Return a copy rescaled to ``rate`` events per unit time.

        What-if sweeps (``scenario.sweep`` over ``arrival_rate``) re-rate
        the scenario's arrival process per grid column through this hook,
        preserving the process family instead of silently substituting an
        exponential.
        """
        raise NotImplementedError(
            f"{type(self).__name__} does not support rate rescaling"
        )

    def draw_spec(self) -> tuple[str, tuple[float, ...]]:
        """Lower to a fused per-event generator: ``(dist_id, params)``.

        The DrawPlan machinery (``core/drawplan.py``, DESIGN.md §12) calls
        this to fuse sampling into the engines; only processes with a
        closed-form per-event transform (inverse CDF or Box–Muller) can
        lower — everything else stays on the staged path.
        """
        raise NotImplementedError("no closed-form per-event transform")

    # Optional analytical handles (paper: user-provided PDF/CDF are compared
    # against simulation histograms by the metrics tools).
    def pdf(self, x: Array) -> Array:  # pragma: no cover - optional
        raise NotImplementedError(f"{type(self).__name__} has no closed-form pdf")

    def cdf(self, x: Array) -> Array:  # pragma: no cover - optional
        raise NotImplementedError(f"{type(self).__name__} has no closed-form cdf")


@dataclasses.dataclass(frozen=True)
class ExpSimProcess(SimProcess):
    """Exponential process with ``rate`` events per unit time."""

    rate: float

    def _raw_sample(self, key, shape):
        return _exp_draw(key, shape, self.rate)

    def mean(self):
        return 1.0 / self.rate

    def with_rate(self, rate):
        return dataclasses.replace(self, rate=float(rate))

    def draw_spec(self):
        return "exp", (self.rate,)

    def pdf(self, x):
        return self.rate * jnp.exp(-self.rate * x)

    def cdf(self, x):
        return 1.0 - jnp.exp(-self.rate * x)


@dataclasses.dataclass(frozen=True)
class DeterministicSimProcess(SimProcess):
    """Fixed-interval process (e.g. cron-style arrivals)."""

    interval: float

    def _raw_sample(self, key, shape):
        del key
        return jnp.full(shape, self.interval, dtype=jnp.float32)

    def mean(self):
        return self.interval

    def with_rate(self, rate):
        return dataclasses.replace(self, interval=1.0 / float(rate))

    def draw_spec(self):
        return "det", (self.interval,)


@dataclasses.dataclass(frozen=True)
class GaussianSimProcess(SimProcess):
    """Gaussian process truncated at ~0 (samples are clamped positive)."""

    mu: float
    sigma: float

    def _raw_sample(self, key, shape):
        return _gauss_draw(key, shape, self.mu, self.sigma)

    def mean(self):
        # Exact truncated-normal mean correction is negligible for mu >> sigma;
        # report the nominal mean as the paper's Gaussian example does.
        return self.mu

    def with_rate(self, rate):
        # Mean-preserving rescale: shift the mean to 1/rate and scale sigma
        # by the same factor, keeping the coefficient of variation.
        f = (1.0 / float(rate)) / self.mu
        return dataclasses.replace(self, mu=self.mu * f, sigma=self.sigma * f)

    def draw_spec(self):
        return "gauss", (self.mu, self.sigma)


@dataclasses.dataclass(frozen=True)
class WeibullSimProcess(SimProcess):
    """Weibull(k, lambda): heavy/light tails beyond the Markovian family."""

    shape_k: float
    scale: float

    def _raw_sample(self, key, shape):
        u = jax.random.uniform(key, shape, minval=1e-12, maxval=1.0)
        return self.scale * (-jnp.log(u)) ** (1.0 / self.shape_k)

    def mean(self):
        from math import gamma

        return self.scale * gamma(1.0 + 1.0 / self.shape_k)

    def with_rate(self, rate):
        from math import gamma

        return dataclasses.replace(
            self, scale=1.0 / (float(rate) * gamma(1.0 + 1.0 / self.shape_k))
        )

    def draw_spec(self):
        return "weibull", (self.shape_k, self.scale)


@dataclasses.dataclass(frozen=True)
class GammaSimProcess(SimProcess):
    shape_k: float
    scale: float

    def _raw_sample(self, key, shape):
        return jax.random.gamma(key, self.shape_k, shape) * self.scale

    def mean(self):
        return self.shape_k * self.scale

    def with_rate(self, rate):
        return dataclasses.replace(self, scale=1.0 / (float(rate) * self.shape_k))


@dataclasses.dataclass(frozen=True)
class LogNormalSimProcess(SimProcess):
    mu: float
    sigma: float

    def _raw_sample(self, key, shape):
        return _lognorm_draw(key, shape, self.mu, self.sigma)

    def mean(self):
        return float(np.exp(self.mu + 0.5 * self.sigma**2))

    def with_rate(self, rate):
        # exp(mu + sigma^2/2) = 1/rate, keeping sigma (shape) fixed.
        return dataclasses.replace(
            self, mu=float(-np.log(rate) - 0.5 * self.sigma**2)
        )

    def draw_spec(self):
        return "lognorm", (self.mu, self.sigma)


@dataclasses.dataclass(frozen=True)
class ParetoSimProcess(SimProcess):
    """Pareto(alpha, x_m): heavy-tailed service times (cold-start spikes)."""

    alpha: float
    x_m: float

    def _raw_sample(self, key, shape):
        u = jax.random.uniform(key, shape, minval=1e-12, maxval=1.0)
        return self.x_m / u ** (1.0 / self.alpha)

    def mean(self):
        if self.alpha <= 1.0:
            return float("inf")
        return self.alpha * self.x_m / (self.alpha - 1.0)

    def with_rate(self, rate):
        # alpha (tail index) is the shape; move the scale x_m so the mean
        # alpha*x_m/(alpha-1) equals 1/rate.  Undefined for alpha <= 1.
        if self.alpha <= 1.0:
            raise ValueError(
                "Pareto with alpha <= 1 has infinite mean; cannot re-rate"
            )
        return dataclasses.replace(
            self, x_m=(self.alpha - 1.0) / (self.alpha * float(rate))
        )

    def draw_spec(self):
        return "pareto", (self.alpha, self.x_m)


@dataclasses.dataclass(frozen=True)
class BatchArrivalProcess(SimProcess):
    """Batch arrivals: groups of ``batch_size`` requests arrive together.

    Inter-arrival samples are 0 for requests within a batch and drawn from
    ``base`` between batches.  This covers the paper's stated gap in
    analytical models ("absence of batch arrival modelling").
    """

    base: SimProcess
    batch_size: int

    def _raw_sample(self, key, shape):
        base_samples = self.base._raw_sample(key, shape)
        n = int(np.prod(shape)) if shape else 1
        flat = base_samples.reshape(-1)
        idx = jnp.arange(n)
        is_batch_head = (idx % self.batch_size) == 0
        out = jnp.where(is_batch_head, flat, 0.0)
        return out.reshape(shape)

    def sample(self, key, shape):
        # Zeros are legal for batch arrivals; bypass the positivity clamp for
        # in-batch members but keep batch-head gaps positive.
        out = self._raw_sample(key, shape).astype(jnp.float32)
        n = int(np.prod(shape)) if shape else 1
        idx = jnp.arange(n).reshape(shape)
        is_head = (idx % self.batch_size) == 0
        return jnp.where(is_head, jnp.maximum(out, _EPS), 0.0)

    def mean(self):
        return self.base.mean() / self.batch_size

    def with_rate(self, rate):
        return dataclasses.replace(
            self, base=self.base.with_rate(float(rate) / self.batch_size)
        )


@dataclasses.dataclass(frozen=True)
class CustomSimProcess(SimProcess):
    """Escape hatch: wrap any ``fn(key, shape) -> samples`` (paper: users can
    pass a random generator function with a custom distribution)."""

    fn: Callable[[Array, tuple[int, ...]], Array]
    mean_value: float
    pdf_fn: Optional[Callable[[Array], Array]] = None
    cdf_fn: Optional[Callable[[Array], Array]] = None

    def __hash__(self):  # Callables keep the dataclass hashable for jit.
        return hash((id(self.fn), self.mean_value))

    def _raw_sample(self, key, shape):
        return self.fn(key, shape)

    def mean(self):
        return self.mean_value

    def pdf(self, x):
        if self.pdf_fn is None:
            raise NotImplementedError
        return self.pdf_fn(x)

    def cdf(self, x):
        if self.cdf_fn is None:
            raise NotImplementedError
        return self.cdf_fn(x)


# ---------------------------------------------------------------------------
# Non-stationary arrivals: rate profiles, NHPP thinning, timestamp streams
# ---------------------------------------------------------------------------

# Inert-arrival sentinel for absolute-timestamp streams: any timestamp past
# the horizon is ignored by the engines (``t > t_end`` arrivals are inert),
# so thinning rejections and padding map here.  Finite so f32 backends can
# carry it without producing inf/nan arithmetic.
PAD_TIME = 1e30


class ArrivalTimeProcess:
    """Mixin for arrival processes that generate *absolute timestamps*.

    The engines detect this interface and switch the scan to the prestamped
    path: the step consumes the arrival clock directly instead of
    accumulating inter-arrival gaps.  This is what makes exact trace replay
    and non-stationary (NHPP) arrivals expressible — neither has i.i.d.
    gaps.

    ``arrival_times(key, shape) -> (times, coverage)``:

    * ``times``  — f64 ``shape`` array, non-decreasing along the last axis;
      entries that carry no arrival are ``PAD_TIME`` (inert past-horizon).
    * ``coverage`` — f64 ``shape[:-1]`` array: the time up to which the
      stream is exact.  The sampling layer raises if any row's coverage is
      below ``sim_time`` (the prestamped analogue of the "arrivals ended
      before sim_time" guard — with padded streams the last timestamp is
      ``PAD_TIME`` and cannot be used for the check).
    """

    def arrival_times(self, key: Array, shape: tuple[int, ...]):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class RateProfile:
    """Time-varying arrival-rate profile r(t) for non-stationary workloads.

    Subclasses implement vectorised ``rate(t)`` plus a constant upper bound
    ``max_rate()`` (the thinning envelope lambda_max).
    """

    def rate(self, t: Array) -> Array:
        raise NotImplementedError

    def max_rate(self) -> float:
        raise NotImplementedError

    def step_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """``(edges, rates)`` f64 arrays of a piecewise-constant profile.

        The batched staging program (``simulator.stage_draw_cells``) takes
        a profile's steps as traced values through this hook, so a re-fit
        profile of the same bin count reuses its compile; a profile with no
        step form raises and is staged cell by cell.
        """
        raise NotImplementedError(
            f"{type(self).__name__} is not piecewise constant"
        )

    def with_rate(self, rate: float) -> "RateProfile":
        """Return a copy rescaled so the *time-averaged* rate is ``rate``.

        The profile's shape (relative bin heights / waveform) is
        preserved; only the overall level moves.  This is the profile
        analogue of :meth:`SimProcess.with_rate` — it lets rate sweeps
        and the online what-if service re-level a fitted profile without
        refitting it.
        """
        raise NotImplementedError(
            f"{type(self).__name__} does not support rate rescaling"
        )


def piecewise_rate(edges, rates, t) -> Array:
    """r(t) of a step profile: ``rates[k]`` on ``[edges[k-1], edges[k])``."""
    edges = jnp.asarray(edges, dtype=jnp.float64)
    idx = jnp.searchsorted(edges, jnp.asarray(t, jnp.float64), side="right")
    return jnp.asarray(rates, dtype=jnp.float64)[idx]


def thin_arrivals(key, shape, lam, rate):
    """NHPP arrival times by thinning (Lewis & Shedler), vectorised.

    Candidates from a homogeneous Poisson at the envelope ``lam``; each is
    kept with probability ``rate(t) / lam``; the kept times sort to the
    front and the rejected ones to ``PAD_TIME``.  Returns ``(times,
    coverage)``, coverage being each row's last candidate time.  ``lam``
    may be a traced value (the batched staging program's).
    """
    k_gap, k_acc = jax.random.split(key)
    gaps = jax.random.exponential(k_gap, shape) / lam
    cand = jnp.cumsum(gaps.astype(jnp.float64), axis=-1)
    u = jax.random.uniform(k_acc, shape)
    accept = u * lam <= rate(cand)
    times = jnp.sort(jnp.where(accept, cand, PAD_TIME), axis=-1)
    return times, cand[..., -1]


@dataclasses.dataclass(frozen=True)
class PiecewiseConstantRate(RateProfile):
    """r(t) = rates[k] on [edges[k-1], edges[k]) with edges[-1] = +inf.

    ``edges`` are the K interior boundaries (ascending, > 0); ``rates`` has
    K+1 entries, the first applying from t=0.  This is the shape of
    real-trace rate fits (e.g. hourly Lambda invocation counts).
    """

    edges: tuple
    rates: tuple

    def __post_init__(self):
        e = np.asarray(self.edges, dtype=np.float64)
        r = np.asarray(self.rates, dtype=np.float64)
        if len(r) != len(e) + 1:
            raise ValueError("need len(rates) == len(edges) + 1")
        if len(e) and ((np.diff(e) <= 0).any() or e[0] <= 0):
            raise ValueError("edges must be positive and strictly increasing")
        if (r <= 0).any():
            raise ValueError("rates must be positive")

    def rate(self, t):
        return piecewise_rate(*self.step_arrays(), t)

    def max_rate(self):
        return float(max(self.rates))

    def step_arrays(self):
        return (
            np.asarray(self.edges, dtype=np.float64),
            np.asarray(self.rates, dtype=np.float64),
        )

    def mean_rate(self) -> float:
        """Time-averaged rate over the covered span.

        Bin weights are the edge-to-edge widths; the final (open) bin is
        weighted by the mean finite-bin width — exact for fitted
        profiles, whose bins are uniform.  With no interior edges the
        profile is constant and its single rate is returned.
        """
        r = np.asarray(self.rates, dtype=np.float64)
        if len(self.edges) == 0:
            return float(r[0])
        e = np.asarray(self.edges, dtype=np.float64)
        widths = np.diff(np.concatenate([[0.0], e]))
        widths = np.concatenate([widths, [widths.mean()]])
        return float((r * widths).sum() / widths.sum())

    def with_rate(self, rate):
        if not rate > 0:
            raise ValueError(f"rate must be > 0, got {rate}")
        f = float(rate) / self.mean_rate()
        return dataclasses.replace(
            self, rates=tuple(float(r) * f for r in self.rates)
        )

    @classmethod
    def fit(
        cls,
        timestamps,
        bin_width: float,
        rate_floor: float = 1e-9,
        n_bins: Optional[int] = None,
    ) -> "PiecewiseConstantRate":
        """Estimate a profile from recorded arrival timestamps.

        The paper's workflow in reverse gear: measure a workload on the
        real platform, bin the arrival instants (e.g. hourly Lambda
        invocation counts → ``bin_width=3600``), and turn per-bin counts
        into per-bin rates — the profile a what-if sweep (or an NHPP
        re-simulation) then consumes, closing the trace → profile →
        what-if loop.

        Input hardening (this runs *live* in the online what-if service,
        so a bad batch must fail loudly instead of poisoning the stream):
        timestamps must be a 1-D, finite, non-negative, sorted array —
        violations raise a pointed ``ValueError`` naming the first
        offending index.  **Empty bins clamp to ``rate_floor``** (default
        ``1e-9``): rates must stay strictly positive for the NHPP
        thinning envelope, so a quiet bin can never produce a zero or
        NaN rate mid-stream.  The final bin's rate extends past the last
        edge, so re-simulating beyond the recorded horizon holds the
        last observed level.

        ``n_bins`` pins the bin count (timestamps are binned over
        ``[0, n_bins * bin_width)``; any timestamp at or past that span
        is rejected).  A pinned bin count gives live re-fits a stable
        profile *shape* tick over tick — only the rate values move —
        which is what keeps the online service's incremental sweeps on
        the compile cache.
        """
        ts = np.asarray(timestamps, dtype=np.float64)
        if ts.ndim != 1 or len(ts) < 1:
            raise ValueError(
                "need a 1-D array of >= 1 arrival timestamps, got shape "
                f"{ts.shape}"
            )
        if not np.isfinite(ts).all():
            bad = int(np.flatnonzero(~np.isfinite(ts))[0])
            raise ValueError(
                f"timestamps must be finite; timestamps[{bad}] = {ts[bad]}"
            )
        if (ts < 0).any():
            bad = int(np.flatnonzero(ts < 0)[0])
            raise ValueError(
                f"timestamps must be >= 0; timestamps[{bad}] = {ts[bad]}"
            )
        diffs = np.diff(ts)
        if (diffs < 0).any():
            bad = int(np.flatnonzero(diffs < 0)[0]) + 1
            raise ValueError(
                "timestamps must be sorted ascending; timestamps"
                f"[{bad}] = {ts[bad]} < timestamps[{bad - 1}] = {ts[bad - 1]}"
            )
        if not bin_width > 0:
            raise ValueError(f"bin_width must be positive, got {bin_width}")
        if not rate_floor > 0:
            raise ValueError(
                f"rate_floor must be positive (rates feed the thinning "
                f"envelope), got {rate_floor}"
            )
        if n_bins is None:
            # half-open bin membership [k·w, (k+1)·w), like metric windows
            n_bins = int(np.floor(ts.max() / bin_width)) + 1
        else:
            n_bins = int(n_bins)
            if n_bins < 1:
                raise ValueError(f"n_bins must be >= 1, got {n_bins}")
            if ts.max() >= n_bins * bin_width:
                raise ValueError(
                    f"timestamps must lie in [0, n_bins * bin_width) = "
                    f"[0, {n_bins * bin_width}); max is {ts.max()}"
                )
        counts, _ = np.histogram(
            ts, bins=n_bins, range=(0.0, n_bins * bin_width)
        )
        rates = np.maximum(counts / bin_width, rate_floor)
        edges = np.arange(1, n_bins) * bin_width
        return cls(edges=tuple(edges), rates=tuple(rates))


@dataclasses.dataclass(frozen=True)
class SinusoidalRate(RateProfile):
    """Diurnal profile r(t) = base * (1 + amplitude * sin(2*pi*t/period + phase)).

    ``amplitude`` in [0, 1) keeps the rate strictly positive.
    """

    base: float
    amplitude: float
    period: float
    phase: float = 0.0

    def __post_init__(self):
        if not (0.0 <= self.amplitude < 1.0):
            raise ValueError("amplitude must be in [0, 1)")
        if self.base <= 0 or self.period <= 0:
            raise ValueError("base rate and period must be positive")

    def rate(self, t):
        t = jnp.asarray(t, jnp.float64)
        return self.base * (
            1.0
            + self.amplitude * jnp.sin(2.0 * np.pi * t / self.period + self.phase)
        )

    def max_rate(self):
        return self.base * (1.0 + self.amplitude)

    def with_rate(self, rate):
        # time-averaged rate over a full period is exactly ``base``
        if not rate > 0:
            raise ValueError(f"rate must be > 0, got {rate}")
        return dataclasses.replace(self, base=float(rate))


@dataclasses.dataclass(frozen=True)
class NHPPArrivalProcess(SimProcess, ArrivalTimeProcess):
    """Non-homogeneous Poisson arrivals with intensity ``profile.rate(t)``.

    Sampled by **vectorised thinning** (Lewis & Shedler): draw the whole
    candidate stream from a homogeneous Poisson at the envelope rate
    lambda_max = ``profile.max_rate()``, accept each candidate at time t
    with probability r(t)/lambda_max, then compact accepted times to the
    front with an ascending sort (rejected candidates map to ``PAD_TIME``
    and are inert).  One ``sort`` replaces the sequential accept/reject
    loop, so a whole [replicas, N] stream is a single fused device program.

    ``mean()`` reports the *candidate* mean gap 1/lambda_max so the
    engines' step-budget heuristic (``steps_needed``) sizes the candidate
    buffer, which is what coverage of the horizon requires.
    """

    profile: RateProfile

    def mean(self):
        return 1.0 / self.profile.max_rate()

    def with_rate(self, rate):
        """Re-level the intensity profile to a time-averaged ``rate``
        (shape-preserving; delegates to ``profile.with_rate``)."""
        return dataclasses.replace(self, profile=self.profile.with_rate(rate))

    def _raw_sample(self, key, shape):
        raise NotImplementedError(
            "NHPP arrivals have no stationary gap distribution; engines "
            "consume them through arrival_times() (prestamped path)"
        )

    def draw_spec(self):
        # Fused NHPP: candidate gaps at the envelope rate, thinning decided
        # inline at the candidate's clock (scan engine only — the block
        # kernels have no profile.rate(t) evaluation).
        return "nhpp", (self.profile.max_rate(),)

    def arrival_times(self, key, shape):
        return thin_arrivals(
            key, shape, self.profile.max_rate(), self.profile.rate
        )


@dataclasses.dataclass(frozen=True)
class MMPPArrivalProcess(SimProcess, ArrivalTimeProcess):
    """Two-phase Markov-modulated Poisson process (bursty arrivals).

    The simulator-side counterpart of ``data/workload.py::mmpp_arrivals``:
    the arrival intensity alternates between ``rate_low`` and
    ``rate_high``, switching phase at exponential(``switch_rate``) epochs,
    starting in the low phase — the canonical doubly-stochastic workload
    closed-form Markovian models cannot express.

    Sampling is NHPP thinning against a *random* per-row rate function:
    each row draws its own switch schedule (cumsum of exponential gaps),
    candidates come from a homogeneous Poisson at the envelope
    ``rate_high``, and candidate t is accepted with probability
    r(t)/rate_high where the phase at t is the parity of switches before
    it.  Everything is one fused device program per ``[replicas, N]``
    buffer — no sequential loop.

    ``mean()`` reports the candidate gap ``1/rate_high`` so
    ``steps_needed()`` sizes the candidate buffer; the switch schedule is
    sized to the same N, so coverage is ``min(candidate, switch)``
    coverage and the draw-time guard catches under-sized buffers (raise
    ``steps`` if ``switch_rate`` is unusually high relative to
    ``rate_high``).
    """

    rate_low: float
    rate_high: float
    switch_rate: float

    def __post_init__(self):
        if self.rate_low <= 0 or self.rate_high <= 0 or self.switch_rate <= 0:
            raise ValueError("MMPP rates must be positive")
        if self.rate_high < self.rate_low:
            raise ValueError("need rate_high >= rate_low (thinning envelope)")

    def mean(self):
        return 1.0 / self.rate_high

    def _raw_sample(self, key, shape):
        raise NotImplementedError(
            "MMPP arrivals have no stationary gap distribution; engines "
            "consume them through arrival_times() (prestamped path)"
        )

    def phase_high(self, switch_times: Array, t: Array) -> Array:
        """Phase at time(s) ``t`` given one row's ascending switch epochs:
        True in the high phase (odd number of switches before t)."""
        n_sw = jnp.searchsorted(switch_times, t, side="right")
        return (n_sw % 2) == 1

    def arrival_times(self, key, shape):
        lam = self.rate_high
        k_gap, k_acc, k_sw = jax.random.split(key, 3)
        gaps = jax.random.exponential(k_gap, shape) / lam
        cand = jnp.cumsum(gaps.astype(jnp.float64), axis=-1)
        sw_gaps = jax.random.exponential(k_sw, shape) / self.switch_rate
        sw = jnp.cumsum(sw_gaps.astype(jnp.float64), axis=-1)
        # per-row phase lookup: rows carry independent switch schedules
        flat_c = cand.reshape(-1, shape[-1])
        flat_s = sw.reshape(-1, shape[-1])
        high = jax.vmap(self.phase_high)(flat_s, flat_c).reshape(shape)
        rate_at = jnp.where(high, self.rate_high, self.rate_low)
        u = jax.random.uniform(k_acc, shape)
        accept = u * lam <= rate_at
        times = jnp.sort(jnp.where(accept, cand, PAD_TIME), axis=-1)
        coverage = jnp.minimum(cand[..., -1], sw[..., -1])
        return times, coverage


@dataclasses.dataclass(frozen=True)
class TraceArrivalProcess(SimProcess, ArrivalTimeProcess):
    """Replay recorded arrival timestamps (the paper's workflow: measure a
    workload on the real platform, feed the trace to the simulator).

    Two replay paths:

    * ``arrival_times`` (preferred; engines detect :class:`ArrivalTimeProcess`
      and switch to the prestamped scan) — the recorded timestamps are fed
      to the simulator *exactly*, in f64, shared across every Monte-Carlo
      replica; only the service-time draws vary per replica.
    * ``sample`` (legacy gap path) — samples are the trace's inter-arrival
      gaps in f32; small cumulative rounding error vs the true timestamps.

    In both paths, if more samples are requested than the trace holds, the
    trace loops (with the wrap gap equal to the mean gap, keeping the rate
    stationary).
    """

    timestamps: tuple  # strictly increasing arrival times

    def __post_init__(self):
        ts = np.asarray(self.timestamps, dtype=np.float64)
        if len(ts) < 2:
            raise ValueError("trace needs >= 2 arrivals")
        if (np.diff(ts) < 0).any():
            raise ValueError("trace timestamps must be non-decreasing")

    def _gaps(self) -> np.ndarray:
        ts = np.asarray(self.timestamps, dtype=np.float64)
        gaps = np.diff(ts)
        return np.concatenate([[ts[0] if ts[0] > 0 else gaps.mean()], gaps])

    def _cycle(self) -> np.ndarray:
        """One replay cycle: the trace gaps followed by the mean-gap wrap."""
        gaps = self._gaps()
        return np.concatenate([gaps, [max(gaps.mean(), 1e-9)]])

    def _raw_sample(self, key, shape):
        del key  # deterministic replay
        n = int(np.prod(shape)) if shape else 1
        cycle = self._cycle()
        reps = int(np.ceil(n / len(cycle)))
        tiled = np.tile(cycle, reps)
        return jnp.asarray(tiled[:n].reshape(shape), dtype=jnp.float32)

    def arrival_times(self, key, shape):
        """Exact absolute-timestamp replay: f64 trace timestamps, identical
        across replicas (the leading axes broadcast the same stream)."""
        del key  # deterministic replay
        *lead, n = shape
        cycle = self._cycle()
        reps = int(np.ceil(n / len(cycle)))
        times = np.cumsum(np.tile(cycle, reps))[:n]
        # The first cycle reproduces the recorded timestamps exactly (the
        # first gap is the recorded time-to-first-arrival).
        ts = np.asarray(self.timestamps, dtype=np.float64)
        if ts[0] > 0:
            times[: len(ts)] = ts[: min(len(ts), n)]
        out = jnp.broadcast_to(
            jnp.asarray(times, dtype=jnp.float64), tuple(lead) + (n,)
        )
        coverage = jnp.full(tuple(lead), np.inf, dtype=jnp.float64)
        return out, coverage

    def mean(self):
        return float(self._gaps().mean())


@dataclasses.dataclass(frozen=True)
class EmpiricalSimProcess(SimProcess):
    """Bootstrap service-time process: resample measured durations (the
    paper's alternative to fitting a parametric distribution)."""

    durations: tuple

    def __post_init__(self):
        d = np.asarray(self.durations, dtype=np.float64)
        if len(d) < 1 or (d <= 0).any():
            raise ValueError("durations must be positive and non-empty")

    def _raw_sample(self, key, shape):
        d = jnp.asarray(np.asarray(self.durations, dtype=np.float32))
        idx = jax.random.randint(key, shape, 0, d.shape[0])
        return d[idx]

    def mean(self):
        return float(np.mean(self.durations))

    def with_rate(self, rate):
        # Rescale every measured duration by the same factor so the
        # bootstrap mean lands on 1/rate (shape of the empirical
        # distribution preserved).
        f = (1.0 / float(rate)) / self.mean()
        return dataclasses.replace(
            self, durations=tuple(float(d) * f for d in self.durations)
        )
