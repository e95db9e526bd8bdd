"""The plain float32 reference agrees with the program's own float32
forward pass at a tiny size, on the same seed's weights; the float8
control does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.models import qwen3_adapter as adapter
from bench.models import qwen3_reference as ref


def _program_logits(cfg, seed, tokens):
    from repro.models.transformer import forward_full
    mcfg = adapter.model_config(cfg)
    params = adapter.make_params(cfg, mcfg, seed)
    logits, _, _ = forward_full(params, mcfg, jnp.asarray(tokens[None]),
                                remat=False)
    return np.asarray(logits[0], np.float32)


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_program_prefill_in_float32(tiny, tied):
    cfg = tiny(dtype="float32", tied=tied)
    seed = 2**31 + 3
    tokens = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], 150).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = _program_logits(cfg, seed, tokens)
    got = np.asarray(ref.row_logits(cfg, seed, [tokens],
                                    [list(range(len(tokens)))]))
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-4, err
    assert float(np.std(want)) == pytest.approx(1.0, rel=0.5)


def test_served_rows_line_up_with_the_served_tokens():
    seq, rows = ref.served_rows(np.array([5, 6, 7]), [8, 9])
    assert seq.tolist() == [5, 6, 7, 8]
    assert rows == [2, 3]          # logits at 7 chose 8, at 8 chose 9


def test_control_is_further_from_the_reference_than_bfloat16(tiny):
    cfg = tiny(dtype="bfloat16", hidden_size=256, intermediate_size=512,
               num_hidden_layers=4, vocab_size=2048)
    tokens = np.random.default_rng(1).integers(
        0, cfg["vocab_size"], 300).astype(np.int32)
    rows = [list(range(100, 300))]
    f32 = np.asarray(ref.row_logits(cfg, 7, [tokens], rows))
    fp8 = np.asarray(ref.row_logits(cfg, 7, [tokens], rows, lowp=True))
    err = np.linalg.norm(fp8 - f32) / np.linalg.norm(f32)
    assert 0.01 < err < 0.5, err
