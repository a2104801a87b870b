"""A whole run on the CPU at reduced size, with the look for a chip
skipped: a sound run comes out correct, and a run whose timed path is
broken underneath comes out not correct, once for each fault a served
cell can have.  (A single-chip cell has no exchange between chips, and a
served batch has no mean over its lanes to take.)"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 17


@pytest.fixture(autouse=True)
def _reduced(monkeypatch):
    tiny.use_reduced_program(monkeypatch)


def _run(name, hook=None):
    return run.run_cell(tiny.cell(name), SEED, 2.0, False, jax.devices(),
                        None, engine_hook=hook)


def token_altered(engine):
    """Every request's third token is replaced as it is produced."""
    seen, step, submit = [], engine.step, engine.submit

    def submit_and_keep(req):
        seen.append(req)
        submit(req)

    def step_and_alter():
        out = step()
        for r in seen:
            if len(r.output) >= 3 and not getattr(r, "_altered", False):
                r.output[2] = (r.output[2] + 1) % engine.cfg.vocab_size
                r._altered = True
        return out

    engine.submit, engine.step = submit_and_keep, step_and_alter


def state_unchanged(engine):
    """Every decode step hands back the KV state it was given."""
    def frozen(fn):
        def call(params, cache, *args):
            keep = jax.tree.map(jnp.copy, cache)
            out = fn(params, cache, *args)
            return (out[0], keep) + tuple(out[2:])
        return call
    engine._decode_n1 = frozen(engine._decode_n1)
    engine._decode_n = frozen(engine._decode_n)
    engine._mixed_step = frozen(engine._mixed_step)


def test_sound_run_is_correct():
    res = _run("stablelm-3b.chat_shared")
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"output_tok_s", "tpot_p95_ms",
                                   "ttft_p95_s", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] == 8


@pytest.mark.parametrize("fault", [token_altered, state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(fault):
    res = _run("starcoder2-3b.code_decode", fault)
    assert not res["correct"], res["check"]
    gap = res["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def _no_result(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "starcoder2-3b.code_decode", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_no_tpu_means_no_result():
    _no_result(ROOT)


def test_benchmark_files_alone_give_no_result(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(tmp_path, {"PYTHONPATH": ""})
