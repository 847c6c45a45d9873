"""Device time per step of what the trace can name of a looped model's
head and loss, as ``head_loss_ms`` reads it, by its patterns at this
cell's shapes: every loop step's normed states go through the one head in
ONE pass of ``parallel.train._head_loss_rows``, so the rows of the batch
are ``total_ut_steps`` x B (8 rows of 8,192 in ``ouro-raw``, under the
49,152-row vocabulary) and a chunk is cut from that. ``head_loss_ms``'s own
list of cells is the benchmark's; this is its reader at the looped rows.
None where the configuration has no loop or the trace none of these
operations."""

from benchmark import readers
from benchmark.metrics import head_loss_ms


def read(run):
    steps = run["cell"].config.get("total_ut_steps")
    if not steps:
        return None
    d = head_loss_ms.dims(run)
    found = head_loss_ms.patterns({**d, "b": d["b"] * steps})
    if not found:
        return None
    return readers.kernel_ms_per_step(run, "|".join(f"(?:{p})" for p in found))
