"""Cells at the size of the program's ``reduced()`` presets, for CPU tests:
the same configuration and traffic files with every size cut down, and
the program's configuration swapped for its reduced twin."""
from __future__ import annotations

import copy
import json

from bench import harness

#: files kept under bench/ for a cell that BENCHMARK.json does not hold
#: yet (PERF.md, open questions): name -> (configuration, traffic mix)
KEPT = {"stablelm-3b.chat_shared": ("stablelm-3b", "chat_shared")}


def full(name: str) -> harness.Cell:
    """A cell of BENCHMARK.json, or one of ``KEPT`` at its full size."""
    if name not in KEPT:
        return harness.load_cell(name)
    config, traffic = KEPT[name]
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    return harness.cell_of(name, harness.BENCH / "configs" / f"{config}.json",
                           traffic, 1, bench)

#: reduced() sizes of both architectures (2 layers, 256 wide, vocab 512)
_SIZES = {"hidden_size": 256, "intermediate_size": 512,
          "num_hidden_layers": 2, "num_attention_heads": 4,
          "vocab_size": 512}
_KV = {"stablelm-3b": 4, "starcoder2-3b": 2}
ENGINE = {"slots": 4, "cache_len": 128, "page_size": 16, "kv_pages": 25,
          "max_batch_tokens": 32, "decode_chunk": 4, "use_pallas": False}


def cell(name: str) -> harness.Cell:
    c = copy.deepcopy(full(name))
    arch = c.config["arch"]
    c.config.update(_SIZES, num_key_value_heads=_KV[arch])
    c.config["engine"].update(ENGINE)
    # the committed limit stands; about 200 served tokens are compared,
    # so that the float8 control meets a near tie (PERF.md)
    c.config["check"].update(sample_tokens=192)
    t = c.traffic
    t["prompt_tokens"] = {"median": 12, "sigma": 0.5, "min": 4, "max": 24}
    t["output_tokens"] = {"median": 24, "sigma": 0.5, "min": 8, "max": 48}
    if "system_prompts" in t:
        t["system_prompts"] = {"count": 2, "tokens": 32, "zipf_s": 1.0}
        t["rate_per_s"] = 4.0
    if "streams" in t:
        t["streams"], t["requests_per_stream"] = 3, 6
    t["drain_s"] = 30
    return c


def use_reduced_program(monkeypatch) -> None:
    """The harness builds the program's configuration from the arch id;
    hand it the reduced preset instead."""
    import repro.configs as configs
    monkeypatch.setattr(configs, "get_config", configs.get_reduced_config)

