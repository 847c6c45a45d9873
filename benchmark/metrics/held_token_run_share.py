"""Of the step's tokens, the share the held dispatch's token side ran
over: the median over the window's steps of the step program's
``moe_held_token_run_share`` (the mean over the expert layers of what each
sows: the tokens of the token tiles run, ceil(tokens that hold a row /
``HELD_ROW_TILE``) tiles, over the T tokens of the step;
``llama._touched_tokens``). It is the engagement of the gate-weighted sum
that brings a token's K rows back (``llama._gather_sum``, forward and as
the transpose of the gather into the buffer): its K row gathers a pass run
over this share of the T tokens, and one placement of [T, H] a pass runs
whatever it reads. A token holds a row where at least one of its K choices
is an expert held here and fitted the buffer, so it reads at most
``*_held_share`` x K rounded up to a tile a layer, and 1 where every token
holds one (a router that floods the held experts, or a step of one tile).

Not in it: what ``held_run_share`` reads (the buffer's side, R rows), the
grouped matmuls, and the argsorts over the T*K assignments and over the T
tokens. None where the step counts no such thing: a cell whose layers hold
all their experts or have none, a trainer that hands no counters over, a
program from before the token side's loop."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "moe_held_token_run_share")
