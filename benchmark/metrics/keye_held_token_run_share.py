"""Of the step's 16,384 tokens, the share the held dispatch's token side
ran over in Keye-VL-2.0's expert layers: the median over the window's steps
of the step program's ``moe_held_token_run_share``, as
``held_token_run_share`` reads it (the tokens of the token tiles run,
ceil(tokens that hold a row / 512) tiles a layer, over the step's tokens).
With 16 of 128 experts held and 8 choices a token, two tokens in three hold
a row under a uniform router: 1 - (112 x 111 x ... x 105) / (128 x 127 x
... x 121) = 0.667."""

from benchmark.metrics.held_token_run_share import read  # noqa: F401
