"""The measured window: whole requests only, back to back, and nothing
traced or compiled inside it."""

import time

import pytest

from bench_helpers import run_tiny, steer


class Sleeper:
    """A mix whose request ``i`` takes ``durations[i]`` seconds."""

    def __init__(self, durations):
        self.durations = durations

    def request(self, i):
        time.sleep(self.durations[i])
        return i

    def phases(self):
        return [("bench.sleep", self.request)]

    def ok(self, result):
        return True


@pytest.mark.parametrize("annotate", [False, True])
def test_window_holds_whole_requests(annotate):
    import harness

    mix = Sleeper([0.08] * 50)
    done, lat, window_s, failed = harness._window(mix, 3, 0.3, annotate=annotate)
    assert [i for i, _ in done] == list(range(3, 3 + len(lat)))
    assert failed == 0
    # it stops at the first request to end past the limit, not before
    assert window_s >= 0.3
    assert sum(lat[:-1]) < 0.3 + 0.02
    # back to back: the window is the requests and nothing else
    assert window_s == pytest.approx(sum(lat), abs=0.01)


def test_failed_request_is_counted():
    import harness

    class Flaky(Sleeper):
        def request(self, i):
            if i == 1:
                raise RuntimeError("boom")
            return super().request(i)

    done, lat, window_s, failed = harness._window(Flaky([0.05] * 20), 0, 0.2, annotate=False)
    assert failed == 1 and len(done) == len(lat) - 1


def _retrace_in_window(monkeypatch, what):
    """Make every request after the warm-up add a trace (``what`` =
    "counter": a program trace counter moves; "compile": JAX compiles)."""
    import jax
    import jax.numpy as jnp

    import mixes
    from repro.core import scenario

    build = mixes.build

    def build_retracing(*a, **k):
        mix = build(*a, **k)
        orig = mix.request

        def request(i):
            out = orig(i)
            if i >= 1:
                if what == "counter":
                    scenario.TRACE_COUNTS["bench_test_retrace"] += 1
                else:
                    jax.jit(lambda x: x * (i + 0.5))(jnp.ones(3)).block_until_ready()
            return out

        mix.request = request
        return mix

    monkeypatch.setattr(mixes, "build", build_retracing)


@pytest.mark.parametrize("what", ["counter", "compile"])
def test_a_trace_inside_the_window_is_not_correct(what, monkeypatch, capsys):
    steer(monkeypatch)
    _retrace_in_window(monkeypatch, what)
    result, out, err = run_tiny("lambda-sweep-pallas", capsys)
    key = "window_new_traces" if what == "counter" else "window_compile_events"
    assert result["checks"][key]["value"] > 0
    assert result["correct"] is False
