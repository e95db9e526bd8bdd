"""A run at a tiny size, past the harness's look for a chip, with the
timed path sound, broken underneath, or replaced by the float8 control:
``correct`` must come out true only for the sound path."""

import math
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench import trace as trace_mod
from bench.tests.conftest import TINY_MIX, tiny_config

CELL = "qwen3-0.6b.docqa-poisson"


def _run(cfg, engine_hook=None, control=False, seconds=8.0):
    cell = harness.load_cell(CELL)
    cell.config, cell.mix = cfg, dict(TINY_MIX)
    res = harness.run_cell(cell, 2**31 + 101, seconds, False,
                           time.perf_counter(), engine_hook=engine_hook,
                           control=control)
    # a window that finished nothing would fail for the wrong reason
    assert math.isfinite(res["checks"]["max_logit_gap"]["value"])
    return res


def _wrap_decode(transform):
    """An engine hook that passes every decode step's arguments and
    results through ``transform(args, (tokens, pools))``."""
    def hook(engine):
        inner = engine._decode_fn

        def step(*args):
            return transform(args, inner(*args))
        engine._decode_fn = step
    return hook


def _state_unchanged(args, out):
    return out[0], args[1]                  # the step's pools, unwritten


def _token_altered(args, out):
    return (out[0] + 1) % 512, out[1]


FAULTS = {"state_unchanged": _state_unchanged,
          "token_altered": _token_altered}


def test_sound_run_is_correct():
    res = _run(tiny_config())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 10
    cell = harness.load_cell(CELL)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_decode_step_is_caught(fault):
    res = _run(tiny_config(), engine_hook=_wrap_decode(FAULTS[fault]))
    assert not res["correct"], res["checks"]


def test_half_the_batch_left_out_is_caught():
    def hook(engine):
        inner = engine._decode_fn

        def step(*args):
            args = list(args)
            odd = (jnp.arange(args[3].shape[0]) % 2 == 1)
            args[3] = jnp.where(odd[:, None], 0, args[3])    # tokens
            args[4] = jnp.where(odd, 0, args[4])             # positions
            return inner(*args)
        engine._decode_fn = step
    res = _run(tiny_config(), engine_hook=hook)
    assert not res["correct"], res["checks"]


def test_float8_control_fails_the_limit():
    cfg = tiny_config(hidden_size=256, intermediate_size=512,
                      num_hidden_layers=4, vocab_size=2048)
    res = _run(cfg, control=True)
    assert res["correct"], res["checks"]
    assert res["control"]["correct"] is False, res["control"]


class _FakeSummary:
    busy_s, window_s, ops = 1.0, 1.0, {0: []}

    def breakdown(self):
        return {"device_ops": [], "idle_gaps": []}


@pytest.mark.parametrize("trace_seconds", [None, 2.0])
def test_trace_seconds_profiles_the_windows_last_stretch(monkeypatch,
                                                         trace_seconds):
    """A configuration's ``trace_seconds`` starts the profiler that long
    before the window closes; without it the whole window is traced."""
    stamps = {}
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k:
                        stamps.setdefault("start", time.perf_counter()))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda:
                        stamps.setdefault("stop", time.perf_counter()))
    monkeypatch.setattr(trace_mod, "load", lambda path: None)
    monkeypatch.setattr(trace_mod, "summarize",
                        lambda profile, chips: _FakeSummary())
    cfg = tiny_config()
    if trace_seconds is not None:
        cfg["trace_seconds"] = trace_seconds
    cell = harness.Cell(CELL, 1, cfg, dict(TINY_MIX), [], [])
    seconds = 6.0
    res = harness.run_cell(cell, 2**31 + 102, seconds, True,
                           time.perf_counter())
    assert res["correct"], res["checks"]
    traced = stamps["stop"] - stamps["start"]
    want = seconds if trace_seconds is None else trace_seconds
    assert want - 1.0 < traced < want + 1.0
