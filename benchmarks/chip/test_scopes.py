"""CPU checks of the readers of the program's own scopes and counters:
device self time by named scope (``scopes.py``, ``head_step_ms``,
``attention_step_ms``) and the window and head fill counters
(``window_fill_pct``, ``head_fill_pct``).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/test_scopes.py
"""
from __future__ import annotations

import types
from pathlib import Path

import jax
import numpy as np
import pytest

import drive
import scopes
import spec
import traffic
from metrics import (attention_step_ms, head_fill_pct, head_step_ms,
                     window_fill_pct)

HERE = Path(__file__).resolve().parent
US = 1e-6


def _handmade():
    from jax.profiler import ProfileData
    from trace_reduce import reduce_profile
    txt = (HERE / "testdata" / "scopes.xspace.txt").read_text()
    return reduce_profile(ProfileData.from_text_proto(txt))


class _Sched:
    """Stands in for the scheduler: only its compiled window step."""

    def window_hlo(self, tenant="A"):
        return (HERE / "testdata" / "scopes.hlo.txt").read_text()


def test_result_shape_drops_layouts():
    assert scopes.result_shape(
        "bf16[4,8]{1,0:T(4,128)(2,1)} fusion(%p), kind=kLoop") == "bf16[4,8]"
    assert scopes.result_shape(
        "(s32[], bf16[4,8]{1,0}) while((s32[], bf16[4,8]{1,0}) %t)"
    ) == "(s32[], bf16[4,8])"


def test_self_seconds_subtracts_nested():
    # a loop 0-10 holding 1-3 and 4-9, which holds 5-6
    start = np.array([0.0, 1.0, 4.0, 5.0, 12.0])
    end = np.array([10.0, 3.0, 9.0, 6.0, 13.0])
    assert scopes.self_seconds(start, end).tolist() == [3.0, 2.0, 4.0,
                                                        1.0, 1.0]


def test_scope_seconds_handmade():
    """Known answers (testdata/scopes.*): attention is its fusion's 80
    us plus the copy nested in the MLP fusion, 20 us; the MLP fusion
    keeps 140 of its 160 us; the eager %fusion.2 of another shape and
    the loop's own 60 us belong to no scope."""
    secs = scopes.scope_seconds(_handmade(), _Sched().window_hlo())
    assert secs == pytest.approx({"attention": 100 * US, "mlp": 140 * US,
                                  "head": 400 * US, "sample": 80 * US})


def test_step_readers_handmade():
    run = types.SimpleNamespace(trace=_handmade(),
                                drv=types.SimpleNamespace(sched=_Sched()))
    assert run.trace.step_busy_s == pytest.approx([560 * US, 240 * US])
    assert head_step_ms.read(run) == pytest.approx(0.2)
    assert attention_step_ms.read(run) == pytest.approx(0.05)


def test_step_readers_silent_without_window_hlo():
    """A program that cannot give its window step's HLO (one built
    before the scopes) reads nothing, and raises nothing."""
    run = types.SimpleNamespace(trace=_handmade(),
                                drv=types.SimpleNamespace(sched=object()))
    assert head_step_ms.read(run) is None
    assert attention_step_ms.read(run) is None


@pytest.mark.parametrize("config", ["qwen3-4b", "qwen3-4b-xbar"])
def test_fill_counters_match_client_steps(config):
    """At a test size, step by step: the program's window counters move
    by the client's own count of useful positions and tokens
    (``drive.Step``), and computed = slots x chunk."""
    from repro.models.model import build_model
    from repro.serve.engine import BatchScheduler
    from test_bench import tiny_conf
    conf = tiny_conf(config)
    mix = spec.load_json(HERE / "mixes" / "chat_short.json")
    mix = dict(mix, prompt=dict(mix["prompt"], max=24),
               output=dict(mix["output"], min=2, max=6))
    cfg = spec.model_config(conf)
    model = build_model(cfg)
    sched = BatchScheduler(model, model.init(jax.random.PRNGKey(0)),
                           n_slots=3, max_len=32, chunk=4)
    drv = drive.Driver(sched)
    for plan in traffic.open_loop(mix, 4.0, 2.0, cfg.vocab, seed=5)[:6]:
        drv.submit(plan, 0.0)
    reg = sched.metrics

    def counts():
        return [reg.total("serve_window_positions_total", kind="useful"),
                reg.total("serve_head_rows_total", kind="emitted")]

    while drv.busy:
        c0 = counts()
        drv.step()
        st = drv.steps[-1]
        assert [b - a for a, b in zip(c0, counts())] == [st.positions,
                                                         st.tokens]
    n = len(drv.steps)
    assert reg.total("serve_window_positions_total",
                     kind="computed") == n * 3 * 4
    run = types.SimpleNamespace(drv=drv)
    assert window_fill_pct.read(run) == pytest.approx(
        100 * sum(s.positions for s in drv.steps) / (n * 12))
    assert head_fill_pct.read(run) == pytest.approx(
        100 * sum(s.tokens for s in drv.steps) / (n * 12))


def test_fill_readers_silent_without_counters():
    from repro.obs import MetricsRegistry
    run = types.SimpleNamespace(drv=types.SimpleNamespace(
        sched=types.SimpleNamespace(metrics=MetricsRegistry())))
    assert window_fill_pct.read(run) is None
    assert head_fill_pct.read(run) is None
