"""Smoke-size cells for the CPU tests: the benchmark's own configuration
and traffic files with every width and length cut down, so that the
harness's whole loop runs on the CPU in seconds."""
from __future__ import annotations

import copy

from bench import spec

SMOKE_MODELS = {
    "olmo-1b-s75": dict(d_model=64, num_layers=2, num_heads=4,
                        num_kv_heads=4, d_ff=128, vocab_size=256,
                        max_seq_len=128),
    "granite-moe-3b-a800m-s75": dict(d_model=64, num_layers=2, num_heads=4,
                                     num_kv_heads=2, d_ff=32,
                                     vocab_size=256, num_experts=5, top_k=2,
                                     max_seq_len=128),
}


#: ``logit_err`` at smoke size, read on the CPU over 12 seeds of the
#: olmo-1b configuration: the program at most 0.047, the float8 control
#: at least 0.311.  (The MoE configuration reads up to 1.1 on 4 of 12
#: seeds, where a bfloat16 router swaps an expert; the tests use a seed
#: on which it does not.)  ``logit_gap`` keeps the cell's own limit.
SMOKE_LOGIT_ERR = {"limit": 0.12, "lower": 0.047, "upper": 0.311}


def smoke_cell(name: str, config: str = "",
               **traffic_over) -> spec.Cell:
    """The cell ``name`` from BENCHMARK.json at smoke size, optionally
    with another configuration of ``bench/configs/``."""
    cell = spec.load_cell(name)
    if config:
        cell.config = spec.load_config(config)
    conf = copy.deepcopy(cell.config)
    conf["model"].update(SMOKE_MODELS[conf["name"]])
    conf["weights"]["std"] = 0.125
    traffic = copy.deepcopy(cell.traffic)
    traffic["engine"] = {"num_slots": 4, "max_len": 96}
    traffic.update(prompt_len={"dist": "loguniform", "min": 4, "max": 20},
                   output_len={"dist": "loguniform", "min": 8, "max": 24},
                   block=4, queue_min=4)
    traffic["sample"] = {"min_tokens": 16, "max_requests": 3, "columns": 64}
    traffic.update(traffic_over)
    cell.config, cell.traffic = conf, traffic
    cell.limits = copy.deepcopy(cell.limits)
    cell.limits["numbers"]["logit_err"] = SMOKE_LOGIT_ERR
    return cell

