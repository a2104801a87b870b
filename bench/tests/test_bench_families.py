"""The family seam: the dense family gives the weights and counts the
benchmark gave before it had families, and a family that exists only as
files under another root runs a whole cell."""
import hashlib
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, run
from bench.harness import BENCH, Record, load_module
from bench.tests import tiny
from bench.weights import make_weights

#: sha256 of (make_weights, to_program) at the reduced size for seed
#: 2**33 + 5.  PARENT: taken before the weights' layout moved into the
#: family module, and still given by the family's layout with the three
#: scales of before (``_parent_scales``): the seam moved no leaf's name,
#: order, shape, mean or draw.  REPAIRED: the family's own layout, whose
#: embedding, query, MLP input and residual projections were rescaled so
#: that the served token depends on the prompt (PERF.md, section 2)
PARENT = {
    "starcoder2-3b.code_decode": (
        "da29324229ae5ebde550c578002b1b3ef63de8a08247a833dbd92e9a5b22b690",
        "f7276ff9f8fa7ffe146b0898822a7799cbe4d8ddf381a6a17adba0f136f21c19"),
    "stablelm-3b.chat_shared": (
        "2f9ac2e49af226e3d8d0b1ea7168e127a95c086c8008488d1009e23f4fdfc1b5",
        "0fb23346345b9974db9dacab19c7f990e1146b8a5d6d24b1ef90b659e61ecb6f"),
}
REPAIRED = {
    "starcoder2-3b.code_decode": (
        "92882d005333677316eda94faaf636104844f4ff1bd71a76706dcc8e394a6def",
        "a9508acf282bc825495c00c1a5c36b2fedc26980d4a545ca79b5fd19720826ea"),
    "stablelm-3b.chat_shared": (
        "c8e62a84490e811caff1b6e091357a3f944e626268114bbb12698f63a5adf225",
        "3de655a32efd326d2cb39c2309989494c55dc3672364d68ee067e6d6c9b16b5b"),
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _parent_scales(spec: dict, m) -> dict:
    """``spec`` with the five rescaled leaves drawn at 1/sqrt(fan-in), as
    before the repair."""
    return dict(spec, embed=(spec["embed"][0], 0.0, m.d ** -0.5),
                wq=(spec["wq"][0], 0.0, m.d ** -0.5),
                wo=(spec["wo"][0], 0.0, (m.H * m.Dh) ** -0.5),
                w1=(spec["w1"][0], 0.0, m.d ** -0.5),
                w2=(spec["w2"][0], 0.0, m.f ** -0.5))


@pytest.mark.parametrize("name", sorted(PARENT))
def test_dense_weights_are_bit_for_bit_as_pinned(name):
    cell = tiny.cell(name)
    fam, m = cell.family, cell.dims
    spec = fam.layout(m)
    w = make_weights(_parent_scales(spec, m), 2**33 + 5)
    assert (_digest(w), _digest(fam.to_program(w, m))) == PARENT[name]
    w = make_weights(spec, 2**33 + 5)
    assert (_digest(w), _digest(fam.to_program(w, m))) == REPAIRED[name]
    changed = {k for k in spec if spec[k] != _parent_scales(spec, m)[k]}
    assert changed == {"embed", "wq", "wo", "w1", "w2"}


def _by_token(runs):
    """(tokens, context sum) token by token: the sums the counts took
    before they took runs."""
    ctx = [first + j for first, n in runs for j in range(n)]
    return len(ctx), sum(ctx)


def _flops_of_sums(m, params, tokens, ctx):
    return 2.0 * params * tokens + 4.0 * m.L * m.H * m.Dh * ctx


def _decode_work_of_sums(m, tokens, ctx):
    return (4.0 * m.L * m.H * m.Dh * ctx,
            float(m.L * 2 * m.K * m.Dh * 2 * ctx
                  + m.L * 2 * m.H * m.Dh * 2 * tokens))


def _random_runs(rng, k):
    return [(int(rng.integers(1, 2049)), int(rng.integers(1, 9)))
            for _ in range(k)]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_dense_counts_over_runs_are_the_counts_over_sums(name):
    cell = tiny.full(name)
    fam, m = cell.family, cell.dims
    params = fam.matmul_params(m)
    rng = np.random.default_rng(3)
    for k in (0, 1, 7, 300):
        runs = _random_runs(rng, k)
        tokens, ctx = _by_token(runs)
        assert fam.model_flops(m, runs) == \
            _flops_of_sums(m, params, tokens, ctx)
        assert fam.paged_decode_work(m, runs) == \
            _decode_work_of_sums(m, tokens, ctx)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_mfu_and_roofline_read_as_they_did_over_sums(name):
    cell = tiny.full(name)
    fam, m = cell.family, cell.dims
    rng = np.random.default_rng(4)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    steps = [_random_runs(rng, 64) for _ in range(9)]
    rec = Record(cell=cell, seed=0, seconds=40.0, open_loop=False,
                 window_s=40.3, peak=peak,
                 decode_runs=_random_runs(rng, 5000),
                 prompt_runs=[(1, int(p)) for p in rng.integers(64, 1025,
                                                                300)],
                 traced_steps=steps,
                 trace={"ops_s": {"flash_decode_paged_bkgd.11": 1.5,
                                  "fusion": 0.6}})
    params = fam.matmul_params(m)
    flops = (_flops_of_sums(m, params, *_by_token(rec.decode_runs))
             + _flops_of_sums(m, params, *_by_token(rec.prompt_runs)))
    mfu = load_module(BENCH / "metrics" / "mfu.py").read(rec)
    assert mfu == 100.0 * flops / (40.3 * 197e12)
    f, b = _decode_work_of_sums(m, *_by_token(
        [r for s in steps for r in s]))
    roof = load_module(BENCH / "metrics" / "paged_decode_roofline.py")
    assert roof.read(rec) == 100.0 * max(f / 197e12, b / 819e9) / 1.5


def _tree(root: Path) -> dict:
    return {str(p.relative_to(root)): p.read_bytes()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_family_in_new_files_alone_runs_a_correct_cell(tmp_path,
                                                         monkeypatch):
    """A configuration naming a family that exists only as a module under
    another root (here a copy of the dense one) runs through
    ``run.run_cell``, and nothing under bench/ changes."""
    tiny.use_reduced_program(monkeypatch)
    before = _tree(BENCH)
    (tmp_path / "bench" / "families").mkdir(parents=True)
    (tmp_path / "bench" / "configs").mkdir()
    (tmp_path / "bench" / "traffic").mkdir()
    shutil.copy(BENCH / "families" / "dense.py",
                tmp_path / "bench" / "families" / "dense_copy.py")
    shutil.copy(BENCH / "traffic" / "code_decode.json",
                tmp_path / "bench" / "traffic")
    config = json.loads((BENCH / "configs" / "starcoder2-3b.json")
                        .read_text())
    config.update(name="coder-copy", family="dense_copy")
    (tmp_path / "bench" / "configs" / "coder-copy.json").write_text(
        json.dumps(config))
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "coder-copy",
                         "file": "bench/configs/coder-copy.json"}]
    bench["workloads"] = [{"name": "coder-copy.code_decode",
                           "config": "coder-copy", "traffic": "code_decode",
                           "chips": 1}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = tiny.shrink(harness.load_cell("coder-copy.code_decode",
                                         root=tmp_path))
    assert Path(cell.family.__file__).parent == tmp_path / "bench" / "families"
    res = run.run_cell(cell, 2**34 + 9, 2.0, False, jax.devices(), None)
    assert res["correct"], res["check"]
    assert res["check"]["tokens_compared"]["value"] >= 150
    assert res["failed"] == 0 and res["attempted"] > 0
    assert _tree(BENCH) == before
