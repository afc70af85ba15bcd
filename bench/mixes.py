"""The benchmark's traffic: what every traffic kind shares, and ``build``.

A traffic file (``bench/traffic/<mix>.json``) names a ``kind`` and its
parameters; a configuration file (``bench/configs/<config>.json``) names
the deployment.  Each kind is a file of its own, ``bench/kinds/<kind>.py``,
found by that name in the run's checkout and loaded by path.  It defines
``Mix(config, traffic, seed)``, whose

* ``request(i)`` sends request ``i`` to the program's public entry point
  and returns its result (the window's unit of work); ``phases()`` is the
  same request as named steps, each traced in a span of its own;
* ``prepare(n)`` draws ahead whatever requests ``0..n-1`` need before the
  window (a stream that exists before the program sees it);
* ``work(i)`` is what request ``i`` asked to simulate, in arrivals:
  the sum over its grid rows of arrival rate times horizon, worked out
  from the request and never from the result;
* ``kernel_bytes(i)``, ``kernel_ops(i)`` the HBM bytes and operations the
  semantics requires a request's kernel to move and do (a roofline's
  numerators), by kernel name;
* ``ok(result)`` whether a result came back whole;
* ``check(done, rng, pool, control)`` compares a sample of the finished
  requests with the kind's plain reference on the draws the timed path
  consumed (``bench/staging.py``) and returns the compared numbers.

A kind's reference is a module-level function that takes ``rnd=`` (the
rounding of a control, ``reference.rounding``) and is run through
``run_reference``, that is ``reference.call``.  It lives in a module of
its own under ``bench/`` (``reference.py`` for ``sweep`` and ``online``)
that imports no JAX: the reference runs in spawned worker processes,
which import it by name and leave the chip to the harness.  A kind file,
loaded by path, is never sent to a worker.

So a new kind is new files: ``bench/kinds/<kind>.py``, its reference
module where the existing ones do not serve, and its test hooks
``tests/bench/kinds/<kind>.py``.  Every size, rate and option a request
uses is read from the two data files, so a new cell of an existing kind
is new data files alone.
"""

from __future__ import annotations

import importlib.util
import math
import os
import pickle
import re
import sys

import jax
import numpy as np

import reference
import staging


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


def process(dist: dict):
    """The program's process for a configuration's distribution entry."""
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


def execution(spec: dict):
    """The program's ``Execution`` plan from a traffic file's entry."""
    from repro.core import Execution

    return Execution(**spec)


def pick(rng, n_items: int, k: int) -> list:
    """``k`` of ``n_items`` indices (all where fewer), drawn from ``rng``, in order."""
    return sorted(rng.choice(n_items, size=min(k, n_items), replace=False).tolist())


def fold_gaps(prog: dict, ref: dict, out: dict) -> None:
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


def compare(pool, fn, jobs, units, control) -> dict:
    """Run the reference ``fn`` over ``jobs`` and fold each unit's gaps.

    A unit is ``(ref_of, prog_of)``: ``ref_of(rows)`` reads the unit's
    metrics off reference rows, ``prog_of()``
    off the program's result.  With ``control`` the reference run at that
    precision takes the program's place."""
    ref_rows = run_reference(pool, fn, jobs, None)
    cand_rows = run_reference(pool, fn, jobs, control) if control else None
    out = {"decision_gap": 0.0, "integral_gap": 0.0}
    for ref_of, prog_of in units:
        fold_gaps(ref_of(cand_rows) if control else prog_of(), ref_of(ref_rows), out)
    return out


DECISION = ("cold_start_prob", "rejection_prob")
INTEGRAL = (
    "avg_server_count",
    "avg_running_count",
    "avg_idle_count",
    "wasted_ratio",
    "avg_response_time",
)


def run_reference(pool, fn, jobs: list, precision) -> list:
    """Run the reference ``fn`` over ``jobs`` (argument tuples), in ``pool``
    when one is given; ``precision`` (a control's) rounds every time it
    computes.  ``fn`` goes to the workers by its module's name, so it is
    pickled here even without a pool: a reference defined in a kind file
    fails on the CPU as it would on the chip."""
    pickle.dumps(fn)
    args = [(fn, job, precision) for job in jobs]
    if pool is None:
        return [reference.call(a) for a in args]
    return pool.map(reference.call, args, chunksize=1)


def kinds(root: str) -> list:
    """The traffic kinds of the checkout at ``root``: ``bench/kinds/*.py``."""
    folder = os.path.join(root, "bench", "kinds")
    names = os.listdir(folder) if os.path.isdir(folder) else []
    return sorted(n[: -len(".py")] for n in names if n.endswith(".py"))


def load_kind(kind: str, root: str):
    """The kind file ``<root>/bench/kinds/<kind>.py``, loaded by path.

    Its reference module is imported by name, here and in the workers, so
    ``<root>/bench`` goes on the path where it is not yet (a run started
    from the checkout has put it there already)."""
    path = os.path.join(root, "bench", "kinds", kind + ".py")
    if not re.fullmatch(r"\w[\w.-]*", kind, re.ASCII) or not os.path.isfile(path):
        raise ValueError(f"unknown traffic kind {kind!r}: no {path}; kinds: {kinds(root)}")
    bench = os.path.join(root, "bench")
    if bench not in sys.path:
        sys.path.append(bench)
    spec = importlib.util.spec_from_file_location("bench_kind_" + re.sub(r"\W", "_", kind), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(config: dict, traffic: dict, seed: int, root: str):
    """The mix of ``traffic``'s kind, from the checkout at ``root``."""
    return load_kind(str(traffic.get("kind")), root).Mix(config, traffic, seed)


LOWER = {"float64": "float32", "float32": "bfloat16"}


def control_precision(config: dict, traffic: dict) -> str:
    """The control's precision: the next below the one the configuration
    states for the traffic's backend."""
    backend = traffic["execution"].get("backend", "scan")
    return LOWER[config["precision"][backend]]
