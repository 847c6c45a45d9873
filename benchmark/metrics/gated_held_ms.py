"""Device time per step of what the trace can name of an expert layer of
three-matrix SiLU-gated experts that holds a share of its experts: the
grouped matmuls (XLA's ``ragged-dot`` kernels, three a layer forward, over
the static row buffer) and, by the shapes only this layer has, the
dispatch around them. trace_reduce keys an operation by its HLO
instruction name and the start of its (first) result type; what reaches
it is an operation whose first result is

- an array of R rows, R the held dispatch's buffer (the program's
  ``held_buffer_rows``; R + 1 where a zero row is appended): the gathers
  into and out of the buffer, SiLU and the gate between the matmuls,
  their gradients;
- a vector of T*K values (T tokens, K experts a token): the two argsorts
  of the assignments, their keys and slots (in ``lfm2-raw`` R = T*K);
- bf16 and shaped like the held experts' stacked weights, [n, H, I] or
  [n, I, H]: the weights' casts to the compute type and their re-tilings
  (the optimizer's own fusions are float32 and are not counted);
- the router's top-k, which XLA runs as a sort over [batch, seq, E].

Not nameable, and so not in it: the gathers that bring a token's K rows
back (results of T rows, like the rest of the block), the router's matmul
and sigmoid. None where the configuration holds all its experts, on a
program without such a layer, or where the trace has none of these
operations. (``expert_held_ms`` reads the same of a configuration that
spells its keys as Nemotron-H does.)"""

from benchmark import readers


def patterns(run):
    cell = run["cell"]
    config, mix = cell.config, cell.mix
    if "expert_parallel_chips" not in config or "num_experts" not in config:
        return None
    try:
        from torchft_tpu.models.llama import held_buffer_rows
    except ImportError:  # a program that has no such layer
        return None
    b, s = int(mix["batch"]), int(mix["seq"])
    cfg = cell.adapter.model_config(config, s)
    rows = held_buffer_rows(cfg, b * s)
    n, h, i = config["num_experts"], config["hidden_size"], config["moe_intermediate_size"]
    first = r"^\S+ \(?"  # the instruction's name, then its (first) result
    return [
        r"^ragged-dot",
        rf"{first}\w+\[(?:{rows}|{rows + 1})[,\]]",
        rf"{first}\w+\[{b * s * cfg.num_experts_per_tok}\]",
        rf"{first}bf16\[{n},(?:{h},{i}|{i},{h})\]",
        rf"^sort\S* \(?f32\[{b},{s},{cfg.num_experts}\]",
    ]


def read(run):
    found = patterns(run)
    if found is None:
        return None
    return readers.kernel_ms_per_step(run, "|".join(f"(?:{p})" for p in found))
