"""``held_token_run_share``: the share of the step's tokens that the held
dispatch's token side ran over, read from the step program's
``moe_held_token_run_share``. On records written by hand, on the table's
entry, and on the arithmetic at the seven cells' published shapes: what a
uniform router's ``*_held_share`` bounds it by, to a tile."""

import math
import os

import pytest

from benchmark import cells
from benchmark.metrics import held_token_run_share

HELD_CELLS = ["nemotron3-raw", "lfm2-raw", "sdar-raw", "joyai-raw", "solar-open2-raw",
              "smallthinker-raw", "trinity-raw"]


def test_the_median_of_the_windows_steps_and_nothing_where_none_counts():
    records = [{"counters": {"moe_held_token_run_share": v, "moe_held_run_share": 0.2}}
               for v in (0.0625, 0.03125, 0.03125)]
    assert held_token_run_share.read({"records": records}) == 0.03125
    # a parent program that has no such loop, a dense cell, a trainer without counters
    parent = [{"counters": {"moe_held_share": 0.1, "moe_held_run_share": 0.2}}]
    assert held_token_run_share.read({"records": parent}) is None
    assert held_token_run_share.read({"records": [{"counters": {}}, {}]}) is None
    assert held_token_run_share.read({"records": []}) is None


def test_the_table_lists_it_for_the_seven_cells_that_hold_a_share():
    table = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in table["per_layer"] if m["name"] == "held_token_run_share"]
    assert entry == {
        "name": "held_token_run_share", "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "expert layer", "moves": "tok_s_chip",
        "workloads": HELD_CELLS,
    }
    names = [m["name"] for m in table["per_layer"]]
    assert names.index("held_token_run_share") > names.index("trinity_gmm_roofline")
    for name in HELD_CELLS:
        cell = cells.load_cell(name)
        assert "held_token_run_share" in {m["name"] for m in cell.per_layer}
        assert cell.adapter.model_config(cell.config, int(cell.mix["seq"])).experts_held
    for name in ("olmoe-raw", "mistral-raw", "olmo-hybrid-raw"):
        per_layer = {m["name"] for m in cells.load_cell(name).per_layer}
        assert "held_token_run_share" not in per_layer


@pytest.mark.parametrize("name,held_share,at_most", [
    # *_held_share of the ledger's PR 65 lines -> ceil(min(T, share * T*K) / 512) * 512 / T
    ("joyai-raw", 1.8e-5, 0.03125),        # 3 assignments: one tile of 32
    ("solar-open2-raw", 5.7e-6, 0.03125),  # 1: one of 32
    ("trinity-raw", 0.0068, 0.0625),       # 892: two of 32
    ("nemotron3-raw", 0.0026, 0.03125),    # 256: one of 32
    ("sdar-raw", 0.083, 0.671875),         # 21,758 of 32,768 rows: 43 of 64
    ("smallthinker-raw", 0.111, 0.6875),   # 10,912 of 16,384: 22 of 32
    ("lfm2-raw", 0.119, 0.5),              # 7,799: 16 of 32
])
def test_what_a_held_share_bounds_it_by_at_the_cells_shapes(name, held_share, at_most):
    """Every token that holds a row holds at least one assignment, so the
    assignments held bound the tokens touched; fewer where a token's
    choices share the chip. The number a chip run is read against
    (PERF.md section 6)."""
    from torchft_tpu.models import llama

    cell = cells.load_cell(name)
    b, s = int(cell.mix["batch"]), int(cell.mix["seq"])
    cfg = cell.adapter.model_config(cell.config, s)
    tokens = b * s * (2 if cfg.objective == "block_diffusion" else 1)
    tile = llama._held_tile(tokens)
    assert tile == llama.HELD_ROW_TILE and tokens % tile == 0
    held = math.ceil(held_share * tokens * cfg.num_experts_per_tok)
    assert -(-min(tokens, held) // tile) * tile / tokens == pytest.approx(at_most)
