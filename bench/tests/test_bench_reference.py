"""The family's plain reference against the program's own forward pass,
at the reduced sizes of both configurations, in float32 on the CPU."""
import dataclasses

import jax
import numpy as np
import pytest

from bench.tests import tiny
from bench.weights import make_weights


@pytest.mark.parametrize("name", ["stablelm-3b.chat_shared",
                                  "starcoder2-3b.code_decode"])
def test_reference_matches_program_forward(name):
    from repro.configs import get_reduced_config
    from repro.configs.base import RunConfig
    from repro.models.model import forward_train
    cell = tiny.cell(name)
    fam, m = cell.family, cell.dims
    cfg = dataclasses.replace(get_reduced_config(cell.config["arch"]),
                              dtype="float32", param_dtype="bfloat16",
                              rope_theta=m.rope_theta)
    assert (cfg.d_model, cfg.num_kv_heads, cfg.qkv_bias) == (m.d, m.K,
                                                             m.qkv_bias)
    w = make_weights(fam.layout(m), 12345)
    tokens = np.random.default_rng(0).integers(2, m.V, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = forward_train(fam.to_program(w, m),
                                {"tokens": tokens[None]}, cfg,
                                RunConfig(remat="none"))
    want = np.asarray(want[0], np.float32)
    got = fam.reference.logits(w, m, tokens, np.arange(len(tokens)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_weights_repeat_for_a_seed_and_differ_across_seeds():
    cell = tiny.cell("stablelm-3b.chat_shared")
    spec = cell.family.layout(cell.dims)
    a, b = make_weights(spec, 2**40 + 3), make_weights(spec, 2**40 + 3)
    c = make_weights(spec, 2**40 + 4)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["wq"]), np.asarray(c["wq"]))


def test_fp8_control_moves_logits_more_than_bf16_rounding():
    """The control rounds every matrix through float8: its logits lie
    further from float32 than the bfloat16-stored weights' do."""
    cell = tiny.cell("stablelm-3b.chat_shared")
    fam, m = cell.family, cell.dims
    w = make_weights(fam.layout(m), 7)
    tokens = np.random.default_rng(1).integers(2, m.V, 30).astype(np.int32)
    rows = np.arange(len(tokens))
    exact = fam.reference.logits(w, m, tokens, rows)
    ctrl = fam.reference.logits(w, m, tokens, rows, fp8=True)
    assert np.abs(ctrl - exact).max() > 1e-2
