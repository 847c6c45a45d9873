"""A cell's model against its architecture's plain reference at a length of
the builder's choosing: loss and every gradient leaf, as
``benchmark/worker.py`` ``reference_check`` compares them on its 1,024-token
sample, but on one seeded sequence of ``--seq`` tokens (the cell's own by
default) through the cell's own model configuration, kernels and all.

Why it exists: the harness's sample is shorter than SmallThinker's window
of 4,096, so its check never sees the band's edge (PERF.md section 7);
this does, at 16,384 tokens, with the reference in blocks of
``--query-block`` query rows so that it fits the chip
(``reference.loss_and_grads(..., query_block=)``; an architecture whose
reference has no such option is compared whole).

``--operand-dtype float8_e4m3fn`` hands the comparison the REFERENCE with
its matmul operands rounded to that type in the system's place: the next
precision down, which a tolerance has to refuse. ``--program-window N``
gives the PROGRAM's windowed layers a window of N positions and leaves the
reference its own: a band that is misplaced or missing, which a tolerance
has to refuse too. ``--departure NAME`` (an architecture whose reference
names its ``DEPARTURES``: afmoe's dropped post-norm, missing embedding
scale, gate on the stream, rotated global layer, bias in the gates) hands
the comparison the reference computing that other model in the system's
place.

    python tools/reference_compare.py --workload smallthinker-raw \\
        --seeds 3000000001,2999999877 --query-block 256 --out chiprun_out/compare.jsonl

For a cell whose attention SELECTS its keys (``keye-raw``: the reference
names its ``selections``) a line also carries ``selection_agreement``: of
the entries the reference's float32 indexer selected in a layer, the share
the program's own indexer (bf16 operands, its passes) selected too, the
least over the layers, and ``selection_agreement_layers``, every layer's in
the layers' order (lengths whose [S, S] fits; neither at the cell's 16,384).
``--departure selection_off_by_<n>`` hands the comparison the reference
attending to a selection whose last n keys a row are exchanged for the next
n, ``selection_random`` to one that owes the indexer nothing: how far off a
selection has to be before a tolerance refuses it.

For a looped model (``ouro-raw``: one stack applied four times) the
timed shape is 8,192 tokens, ``--query-block 512`` keeps the reference's
[16, S, S] scores and its four steps' full logits inside the chip, and the
departures are ``unshared`` (a layer's gradient its last visit's alone),
``norm_outside``, ``no_entropy`` and ``gate_entropy_only`` (the gate
learning from the entropy term alone). ``--leaves exit_gate`` adds
``leaf_readings``, every leaf's reading whose path holds that text: the
gate's leaf (weights and, as its last row, the bias) learns from small
differences and is read beside the worst leaf before a limit is set.

One JSON line a seed; exit code 1 where a tolerance is passed. Through the chip
tool at the published widths; on the CPU only at a test's size
(``compare`` is what the tests call).
"""

from __future__ import annotations

import argparse
import dataclasses
import inspect
import json
import os
import sys
from typing import Any, Dict, Iterator, Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


# The longest sequence whose every layer's [S, S] selection is compared.
AGREEMENT_MAX_SEQ = 4096
# A cell's sample's init (a length and sample configuration) and its sound
# reference (a length and query block), each compiled once a process.
_COMPILED_ONCE: Dict[Any, Any] = {}


def selection_agreement(cell: Any, cfg: Any, mesh: Any, params: Any, sample: Dict) -> Optional[list]:
    """A share a published layer, in the layers' order: of the entries the
    reference selected there, the share the program's own indexer (its
    parameters, its dtype, its passes) selected too. None for an
    architecture that selects nothing."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.llama import Indexer
    from torchft_tpu.ops import sparse_index as dsa
    from torchft_tpu.parallel.train import build_model

    seq = sample["inputs"].shape[1]
    if not hasattr(cell.reference, "selections") or seq > AGREEMENT_MAX_SEQ:
        return None
    model = build_model(dataclasses.replace(cfg, remat=False), mesh)
    _, state = model.apply(
        {"params": params}, sample["inputs"], mutable=["intermediates"],
        capture_intermediates=lambda module, _: isinstance(module, Indexer),
    )
    # By the layer's NUMBER: a tree's keys come sorted as strings, layers_10
    # ahead of layers_2, and the reference's come in the layers' order.
    found = {
        int(name.split("_")[1]): layer["attn"]["indexer"]["__call__"][0]
        for name, layer in state["intermediates"].items() if "attn" in layer
    }
    want = cell.reference.selections(params, sample, cell.config)
    assert len(found) == len(want), (sorted(found), len(want))
    shares = []
    for number, kept in zip(sorted(found), want):
        q_index, k_index, weights = found[number]
        words = dsa.select(dsa.index_scores(q_index, k_index, weights), cfg.sparse_topk, seq, seq)[0]
        both = jnp.sum(dsa.unpack(words, seq) & kept)
        shares.append(float(both / jnp.sum(kept)))
    return shares


def comparisons(
    cell: Any, seq: int, seeds: Sequence[int], query_block: Optional[int] = None,
    operand_dtype: Optional[str] = None, program_window: Optional[int] = None,
    departure: Optional[str] = None, leaves: Optional[str] = None,
) -> Iterator[Dict[str, Any]]:
    """One comparison a seed (weights and tokens from it), its readings
    beside the reference's tolerances; the programs are compiled once."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.parallel import auto_mesh
    from torchft_tpu.parallel.train import build_model, make_grad_step, state_shardings

    config, reference = cell.config, cell.reference
    # As the harness's check builds its sample's model: the kernels are
    # taken wherever the cell takes them, also at a shorter length.
    cfg = cell.adapter.sample_config(cell.adapter.model_config(config, seq), seq)
    if program_window is not None:
        cfg = dataclasses.replace(cfg, sliding_window=program_window)
    mesh = auto_mesh(1, devices=jax.devices()[:1])
    model = build_model(cfg, mesh)
    shardings = state_shardings(model, mesh, (1, seq))
    options = {}
    if query_block and "query_block" in inspect.signature(reference.loss_and_grads).parameters:
        options["query_block"] = query_block
    # One process compares one cell's shape several ways (the system, the
    # reference in a lower precision, a departure): the sample's init and
    # the sound reference are the same programs each time.
    def once(key, make):
        if key not in _COMPILED_ONCE:
            _COMPILED_ONCE[key] = (cell, make())  # the cell kept alive: its id is in the key
        return _COMPILED_ONCE[key][1]

    init = once(("init", id(cell), seq, cfg), lambda: jax.jit(
        lambda rng, tokens: model.init(rng, tokens)["params"],
        out_shardings=shardings.params,
    ))
    ref = once(("reference", id(cell), seq, options.get("query_block")), lambda: jax.jit(
        lambda p, b: reference.loss_and_grads(p, b, config, **options)))
    if operand_dtype is None and departure is None:
        system = make_grad_step(model, mesh, shardings)
    else:
        other = dict(options)
        if operand_dtype is not None:
            other["operand_dtype"] = jnp.dtype(operand_dtype)
        if departure is not None:
            other["departure"] = departure
        system = jax.jit(lambda p, b: reference.loss_and_grads(p, b, config, **other))
    rel = jax.jit(lambda a, b: jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))
    for seed in seeds:
        toks = jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(seed), 0x5EED), (1, seq + 1), 0,
            cfg.vocab_size,
        )
        sample = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
                  "mask": jnp.ones((1, seq), jnp.int32)}
        params = init(jax.random.PRNGKey(seed), sample["inputs"])
        agreement = (
            selection_agreement(cell, cfg, mesh, params, sample)
            if operand_dtype is None and departure is None else None
        )
        loss_sys, g_sys = system(params, sample)
        # To the host: the two gradient trees never share the device's memory.
        loss_sys, g_sys = float(loss_sys), jax.tree_util.tree_map(np.asarray, g_sys)
        loss_ref, g_ref = ref(params, sample)
        del params
        errs = {
            jax.tree_util.keystr(path): float(rel(a, b))
            for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(g_sys), jax.tree_util.tree_leaves(g_ref))
        }
        del g_sys, g_ref
        worst = max(errs, key=lambda k: errs[k] if errs[k] == errs[k] else -1.0)
        loss_rel = abs(loss_sys - float(loss_ref)) / abs(float(loss_ref))
        ranked = sorted(v for v in errs.values() if v == v)
        yield {
            "tokens": seq,
            "seed": seed,
            "compared": (
                f"reference in {operand_dtype}" if operand_dtype is not None
                else f"reference under {departure}" if departure is not None
                else "system" if program_window is None
                else f"system under a window of {program_window}"
            ),
            "query_block": options.get("query_block"),
            "loss_system": loss_sys,
            "loss_reference": float(loss_ref),
            "loss_rel_diff": loss_rel,
            "grad_rel_l2_worst": errs[worst],
            "grad_rel_l2_worst_leaf": worst,
            "grad_rel_l2_second": ranked[-2] if len(ranked) > 1 else None,
            "grad_rel_l2_median": ranked[len(ranked) // 2],
            "leaves": len(errs),
            **({} if leaves is None else {
                "leaf_readings": {k: v for k, v in errs.items() if leaves in k}}),
            **({} if agreement is None else {
                "selection_agreement": min(agreement), "selection_agreement_layers": agreement}),
            "loss_rel_tol": reference.LOSS_REL_TOL,
            "grad_rel_l2_tol": reference.GRAD_REL_L2_TOL,
            "ok": bool(loss_rel <= reference.LOSS_REL_TOL
                       and errs[worst] <= reference.GRAD_REL_L2_TOL),
            "device": jax.devices()[0].device_kind,
        }


def compare(cell: Any, seq: int, seed: int, **options: Any) -> Dict[str, Any]:
    """``comparisons`` for one seed."""
    return next(comparisons(cell, seq, [seed], **options))


def main() -> int:
    from _train_common import enable_compile_cache
    from benchmark import cells

    enable_compile_cache()  # one call makes several comparisons of one reference

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seq", type=int, default=0, help="0: the cell's own")
    ap.add_argument("--seeds", default="0", help="comma-separated")
    ap.add_argument("--query-block", type=int, default=0)
    ap.add_argument("--operand-dtype", default=None)
    ap.add_argument("--program-window", type=int, default=0)
    ap.add_argument("--departure", default=None)
    ap.add_argument("--leaves", default=None, help="also report the leaves whose path holds this")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    cell = cells.load_cell(args.workload)
    ok = True
    for out in comparisons(
        cell, args.seq or int(cell.mix["seq"]), [int(x) for x in args.seeds.split(",")],
        args.query_block or None, args.operand_dtype, args.program_window or None,
        args.departure, args.leaves,
    ):
        line = json.dumps(out)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
        ok = ok and out["ok"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
