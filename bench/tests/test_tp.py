"""The harness's tensor-parallel path at tp=4 on virtual CPU devices, run
by ``tp4_child.py`` in a process of its own: weights born in their shards
equal the one-device draw; a tiny backlog cell is ``correct``, keeps its
queue ``depth`` deep and its pools in their shards; each fault the cell
can have, and the float8 control, comes out not correct."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CHILD = Path(__file__).resolve().parent / "tp4_child.py"


@pytest.fixture(scope="module")
def cases():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, str(CHILD)], env=env,
                       capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-4000:]
    out = [json.loads(line) for line in p.stdout.splitlines()
           if line.startswith("{")]
    return {c.pop("case"): c for c in out}


def test_weights_born_in_shards_equal_the_one_device_draw(cases):
    w = cases["weights"]
    assert all(w["same"])
    # every matrix is split four ways; the embedding table and the norm
    # scales are held whole on every device
    split = [held == [size // 4] * 4 for size, held in w["split"]]
    whole = [held == [size] * 4 for size, held in w["split"]]
    assert all(s or h for s, h in zip(split, whole))
    assert sum(split) == 8          # wq wk wv wo, gate up down, head


def test_sound_tp4_backlog_is_correct(cases):
    res = cases["sound"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["device"]["count"] == 4
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}


def test_backlog_keeps_depth_waiting_before_every_step(cases):
    waiting = cases["sound"]["waiting"]
    assert len(waiting) > 20 and set(waiting) == {6}


def test_pools_stay_in_their_kv_head_shards(cases):
    """After the window each pool leaf is held as four shards of one KV
    head each, never whole on one device."""
    for shape, shards in cases["sound"]["pool_shards"]:
        assert shards == [[shape[0], shape[1], shape[2] // 4] + shape[3:]]


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered",
                                   "half_batch", "exchange_left_out"])
def test_fault_on_the_tp_path_is_caught(cases, fault):
    res = cases[fault]
    assert res["checks"]["max_logit_gap"]["value"] < float("inf")
    assert not res["correct"], res["checks"]


def test_float8_control_fails_on_the_tp_path(cases):
    res = cases["control"]
    assert res["correct"], res["checks"]
    assert res["control"]["correct"] is False, res["control"]
