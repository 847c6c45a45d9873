"""``held_run_share``: the share of the held dispatch's row buffer that its
row-tile loops ran, read from the step program's ``moe_held_run_share``.
On records written by hand, on the table's entry, and on the program's own
count at the six cells' published shapes: what ``*_held_share`` predicts,
to a tile."""

import math
import os

import pytest

from benchmark import cells
from benchmark.metrics import held_run_share

HELD_CELLS = ["nemotron3-raw", "lfm2-raw", "sdar-raw", "joyai-raw", "solar-open2-raw",
              "smallthinker-raw"]


def test_the_median_of_the_windows_steps_and_nothing_where_none_counts():
    records = [{"counters": {"moe_held_run_share": v, "moe_held_share": 0.1}}
               for v in (0.25, 0.19921875, 0.203125)]
    assert held_run_share.read({"records": records}) == 0.203125
    # a parent program that has no such loop, a dense cell, a trainer without counters
    assert held_run_share.read({"records": [{"counters": {"moe_held_share": 0.1}}]}) is None
    assert held_run_share.read({"records": [{"counters": {}}, {}]}) is None
    assert held_run_share.read({"records": []}) is None


def test_the_table_lists_it_for_the_six_cells_that_hold_a_share():
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in table["per_layer"] if m["name"] == "held_run_share"]
    assert entry == {
        "name": "held_run_share", "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "expert layer", "moves": "tok_s_chip",
        "workloads": HELD_CELLS,
    }
    assert table["per_layer"][-1] is entry  # appended: nothing before it moved
    for name in HELD_CELLS:
        cell = cells.load_cell(name)
        assert "held_run_share" in {m["name"] for m in cell.per_layer}
        assert cell.adapter.model_config(cell.config, int(cell.mix["seq"])).experts_held
    for name in ("olmoe-raw", "mistral-raw", "olmo-hybrid-raw"):
        assert "held_run_share" not in {m["name"] for m in cells.load_cell(name).per_layer}


@pytest.mark.parametrize("name,held_share,reads", [
    # *_held_share of the ledger's PR 63 lines -> ceil(share * T*K / 512) * 512 / R
    ("sdar-raw", 0.0977, 0.19921875),      # 25,611 rows of 131,072: 51 tiles of 256
    ("lfm2-raw", 0.25, 0.25),              # 16,384 of 65,536: 32 tiles of 128
    ("smallthinker-raw", 0.11, 0.22916666666666666),  # 10,814 of 49,152: 22 of 96
    ("nemotron3-raw", 0.0068, 0.041666666666666664),  # 669 of 24,576: 2 tiles of 48
    ("joyai-raw", 1.3e-6, 0.03125),        # 1 row of 16,384: one tile of 32
    ("solar-open2-raw", 1e-5, 0.038461538461538464),  # 2 rows of 13,312: one of 26
])
def test_what_a_held_share_predicts_at_the_cells_shapes(name, held_share, reads):
    """The arithmetic the reader's docstring states, at each cell's R and
    tile: the number the chip run is read against (PERF.md section 6)."""
    from torchft_tpu.models import llama

    cell = cells.load_cell(name)
    b, s = int(cell.mix["batch"]), int(cell.mix["seq"])
    cfg = cell.adapter.model_config(cell.config, s)
    tokens = b * s * (2 if cfg.objective == "block_diffusion" else 1)
    rows = llama.held_buffer_rows(cfg, tokens)
    tile = llama._held_tile(rows)
    assert tile == llama.HELD_ROW_TILE and rows % tile == 0
    filled = math.ceil(held_share * tokens * cfg.num_experts_per_tok)
    assert -(-filled // tile) * tile / rows == pytest.approx(reads)
