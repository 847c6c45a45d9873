"""Device time per step of what the trace can name of Keye-VL-2.0's expert
layers (three-matrix SiLU-gated experts, 16 of 128 held), as
``gated_held_ms`` reads it, by its patterns at this cell's shapes: the
``ragged-dot`` kernels, the operations over the buffer's 65,536 rows, the
vectors of the 131,072 assignments, the held weights' bf16 casts and
re-tilings [16, 2048, 768], the router's sort over [1, 16384, 128]. Not
in it, as there: the token side's row tiles (results of 512 rows, which
this cell's selection has too: ``dsa_select_ms`` says how the two are told
apart), the router's matmul."""

from benchmark.metrics.gated_held_ms import read  # noqa: F401
