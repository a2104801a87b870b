"""Cells at the size of the program's ``reduced()`` presets, for CPU tests:
the same configuration and traffic files with every size cut down (the
model's by its family's ``tiny``), and the program's configuration
swapped for its reduced twin."""
from __future__ import annotations

import copy
import dataclasses
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


ENGINE = {"slots": 4, "cache_len": 128, "page_size": 16, "kv_pages": 25,
          "max_batch_tokens": 32, "decode_chunk": 4, "use_pallas": False}


def cell(name: str) -> harness.Cell:
    return shrink(full(name))


def shrink(c: harness.Cell) -> harness.Cell:
    """``c`` with its configuration cut to its family's ``tiny`` sizes and
    its engine and traffic to a few seconds' work on the CPU."""
    config, t = copy.deepcopy(c.config), copy.deepcopy(c.traffic)
    config.update(c.family.tiny(config))
    config["engine"].update(ENGINE)
    # the family's limit for its reduced size stands; about 200 served
    # tokens are compared, so that the float8 control meets a near tie
    config["check"].update(sample_tokens=192)
    t["prompt_tokens"] = {"median": 12, "sigma": 0.5, "min": 4, "max": 24}
    t["output_tokens"] = {"median": 24, "sigma": 0.5, "min": 8, "max": 48}
    if "system_prompts" in t:
        t["system_prompts"] = {"count": 2, "tokens": 32, "zipf_s": 1.0}
        t["rate_per_s"] = 4.0
    if "streams" in t:
        t["streams"], t["requests_per_stream"] = 3, 6
    t["drain_s"] = 30
    return dataclasses.replace(c, config=config, traffic=t)


def use_reduced_program(monkeypatch) -> None:
    """The harness builds the program's configuration from the arch id;
    hand it the reduced preset instead."""
    import repro.configs as configs
    monkeypatch.setattr(configs, "get_config", configs.get_reduced_config)

