"""Device time per step of a learned sparse attention's selection and its
packing (``torchft_tpu/ops/sparse_index.py`` ``select``): for every row
the exact ``topk``-th largest index score by a bisection over the float's
ordered bits (32 counts of a row), the ties' cut by a second over the
columns, the row's log-sum-exp over its selection, the bits packed into
words and the table of tile pairs that hold any. Once a layer and step:
remat keeps the selection. What the trace can name (``dsa_index_ms`` says
how an operation is keyed): a Pallas kernel named ``dsa_select...`` (none
today: the form is ``jax.numpy``'s, whose 32 counts of a chunk XLA fuses);
an operation whose first result is a chunk of C = 512 rows against all S
keys with no heads' axis, [C, S] under unit leading axes (the chunk of I the
selection reads, its ordered bits, the kept entries), or the packed words
[C, W] and [S, W], or an int32 or float32 vector of the chunk's rows, [C]
(the counts and the thresholds of the bisections, the rows' maxima and
sums).

The vectors are not the selection's alone: the held expert dispatch walks
its buffer in row tiles of 512 too (``llama.HELD_ROW_TILE``) and leaves
[512] vectors of every type in the same step. Of those, the pred and
uint32 ones are left out by type, and the int32 and float32 ones by the
names XLA gives the held dispatch's fusions (``HELD_VECTORS``: its
``slice_reduce``, ``multiply_reduce`` and ``dynamic-slice_convert``
fusions). What stays in that is not the selection's: a ``fusion.N
f32[512]`` of the held dispatch's backward, a gather, which shares name and
type with the selection's rows' sums (0.68 of 98.3 ms a step in
``keye-raw``: PERF.md section 5 lists every operation matched, by the
compiled step's own scopes). None where the configuration has no
``sa_config`` or the trace none of these operations."""

from benchmark import readers
from benchmark.metrics import dsa_index_ms

KERNELS = r"^dsa_select"
FIRST = dsa_index_ms.FIRST
# The held dispatch's own [512] vectors of the types the selection's have.
HELD_VECTORS = r"(?!slice_reduce_fusion|multiply_reduce_fusion|dynamic-slice_convert_fusion)"


def patterns(d):
    c, s = d["c"], d["s"]
    widths = "|".join(str(s // n) for n in (32, 16, 8) if s % n == 0)
    return [
        KERNELS,
        rf"{FIRST}\[(?:1,)*{c},{s}\]",
        rf"^{HELD_VECTORS}\S+ \(?(?:s32|f32)\[(?:1,)*{c}\]",
        rf"^\S+ \(?s32\[(?:1,)*(?:{c}|{s}),(?:{widths})\]",
    ]


def read(run):
    d = dsa_index_ms.dims(run)
    if d is None:
        return None
    return readers.kernel_ms_per_step(run, "|".join(f"(?:{p})" for p in patterns(d)))
