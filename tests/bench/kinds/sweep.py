"""Test hooks of the ``sweep`` traffic kind (``bench/kinds/sweep.py``)."""

import mixes
from bench_helpers import plant_in_sweep as plant  # noqa: F401  (a request is one scenario.sweep)


def tiny(config: dict, traffic: dict) -> None:
    """Two values an axis, two replicas, a 300 s horizon, two cells checked."""
    traffic["axes"] = {
        n: [v["linspace"][0], v["linspace"][1]] if isinstance(v, dict) else v[:2]
        for n, v in traffic["axes"].items()
    }
    traffic["replicas"] = 2
    traffic["sim_time"] = 300.0
    traffic["check"]["cells"] = 2


def asked_work(config: dict, traffic: dict) -> float:
    """Every grid row's arrival rate times the horizon."""
    rates = mixes.axis_values(traffic["axes"]["arrival_rate"])
    n_thr = len(mixes.axis_values(traffic["axes"]["expiration_threshold"]))
    return n_thr * traffic["replicas"] * traffic["sim_time"] * sum(rates)
