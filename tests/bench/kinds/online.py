"""Test hooks of the ``online`` traffic kind (``bench/kinds/online.py``)."""

from bench_helpers import plant_in_sweep as plant  # noqa: F401  (a tick runs scenario.sweep)


def tiny(config: dict, traffic: dict) -> None:
    """Four bins, 60 s observed a tick, at most 40 ticks, four checked."""
    traffic["service"]["n_bins"] = 4
    traffic["observe_seconds"] = 60.0
    traffic["max_requests"] = 40
    traffic["check"]["ticks"] = 4


def asked_work(config: dict, traffic: dict) -> float:
    """A tick's work is not counted in arrivals: the cell reports latency."""
    return 0.0
