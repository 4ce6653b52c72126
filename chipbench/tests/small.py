"""Small copies of the benchmark's cells, for the CPU tests: the same
files and code paths at tiny widths, short lengths and few slots."""
from __future__ import annotations

import copy
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent.parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import harness as hz  # noqa: E402

CELLS = ("qwen3-1.7b.chat", "qwen3-1.7b.offline")
#: limit of the served logit gap at these widths (CPU, seeds 1-8, 3 s
#: windows): the program read 0 to 0.0292 and the fp8 control 0.289 to
#: 0.738
SMALL_LIMIT = 0.1
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def small_cell(name: str, **model) -> hz.Cell:
    """``name`` at test widths; ``model`` overrides keys of its model
    block."""
    cell = hz.load_cell(name)
    c = copy.deepcopy(cell.config)
    m = c["model"]
    m.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab=256, max_seq=4096, **model)
    c["serving"] = dict(c["serving"], slots=4, max_kv=64)
    mix = copy.deepcopy(cell.mix)
    mix["prompt_len"] = dict(mix["prompt_len"], median=12, min=3, max=40)
    mix["output_len"] = dict(mix["output_len"], median=10, min=2, max=24)
    mix["preroll_s"] = 0.5
    mix["trace_s"] = 0.5
    if mix["arrival"]["process"] == "backlog":
        mix["arrival"] = dict(mix["arrival"], count=200)
    else:
        mix["arrival"] = dict(mix["arrival"], rate_rps=6.0)
    c["check"] = dict(c["check"], served_logit_gap=SMALL_LIMIT)
    return hz.Cell(cell.name, cell.workload, c, mix, cell.end_to_end,
                   cell.per_layer)
