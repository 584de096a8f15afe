"""The traced span of a `--trace 1` run is bounded in scheduler steps
as well as in seconds: `run.drive` on a system whose step takes what
the test says, the profiler's start and stop replaced by counters."""

import time
import types

import jax
import pytest

from cellbench import run as cb_run
from cellbench import traffic_gen as tg

MIX = {"generator": "closed_loop",
       "prompt": {"dist": "uniform", "min": 4, "max": 12},
       "output": {"dist": "uniform", "min": 400, "max": 800},
       "max_total": 1024}


class SteppingSystem:
    """As much of an adapter's `System` as `drive` touches: every
    step takes ``step_s`` and gives each request one token."""

    def __init__(self, step_s):
        self.step_s = step_s
        self.live = []

    def submit(self, prompt, max_new, due, on_token):
        req = types.SimpleNamespace(left=max_new, on_token=on_token,
                                    t_admitted=time.monotonic())
        self.live.append(req)
        return req, None

    def has_work(self):
        return bool(self.live)

    def step(self):
        time.sleep(self.step_s)
        for req in self.live:
            req.left -= 1
            req.on_token(req, 0)
        self.live = [r for r in self.live if r.left]
        return {"active": len(self.live), "admitted": 0}

    @staticmethod
    def admitted_at(req):
        return req.t_admitted

    @staticmethod
    def used_pages():
        return 0


@pytest.fixture
def profiler(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    return calls


def traced(step_s, seconds, monkeypatch, trace_steps=20):
    monkeypatch.setattr(cb_run, "TRACE_STEPS", trace_steps)
    monkeypatch.setattr(cb_run, "TRACE_S", 0.6)
    plan = tg.make_plan(MIX, {"clients": 3, "rounds": 2}, 5, 100, 2.0)
    d = cb_run.drive(SteppingSystem(step_s), plan, 0.1, seconds,
                     trace_dir="unused")
    a, b = d.trace_span
    return d, [s for s in d.steps if a <= s[0] < b]


def test_short_steps_end_the_span_at_trace_steps(profiler, monkeypatch):
    d, steps = traced(0.002, 1.0, monkeypatch)
    assert profiler == ["start", "stop"]
    assert len(steps) == 20
    a, b = d.trace_span
    # it started where it always did, and stopped well inside the window
    assert a == pytest.approx(d.end - 0.6, abs=0.05)
    assert b < d.end - 0.3
    # the window went on after the stop
    assert sum(s[0] >= b and s[0] < d.end for s in d.steps) > 50


def test_long_steps_end_the_span_at_the_windows_end(profiler,
                                                    monkeypatch):
    d, steps = traced(0.06, 1.0, monkeypatch)
    assert profiler == ["start", "stop"]
    assert 4 <= len(steps) <= 11            # 0.6 s of 60 ms steps
    a, b = d.trace_span
    assert a == pytest.approx(d.end - 0.6, abs=0.07)
    assert d.end <= b < d.end + 0.07        # the step that crossed it


def test_an_untraced_drive_never_touches_the_profiler(profiler,
                                                      monkeypatch):
    monkeypatch.setattr(cb_run, "TRACE_STEPS", 20)
    plan = tg.make_plan(MIX, {"clients": 3, "rounds": 2}, 5, 100, 2.0)
    d = cb_run.drive(SteppingSystem(0.002), plan, 0.1, 0.5)
    assert profiler == [] and d.trace_span is None
    assert d.stop_trace_s == 0.0


def test_the_bound_is_one_constant_for_every_cell():
    assert cb_run.TRACE_STEPS == 120 and cb_run.TRACE_S == 20.0
