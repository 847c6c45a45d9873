"""The smallest decay a Kimi delta mixer applied to one key channel of one
state in one position: the SMALLEST over the window's steps of the step
program's ``kda_decay_min`` = exp(the least log-decay g of the step), over
the step's mixers, heads, channels and positions. 1 leaves a channel as it
was, 0 forgets it in one position. It says how far inside a chunk of 64
the factored form exp(G_i) x exp(-G_j) would be from float32's range (at
the initial values a channel loses up to -1.6 a position in the log, and
the random low-rank projection adds to that), which is why the program's
chunk works in sub-blocks and differences. None on a program whose step
counts no such thing."""


def read(run):
    vals = [r["counters"]["kda_decay_min"] for r in run["records"]
            if "kda_decay_min" in r.get("counters", {})]
    return min(vals) if vals else None
