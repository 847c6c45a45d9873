"""The entropy of a looped model's exit distribution, nats a token: the
median over the window's steps of the step program's ``loop_exit_entropy``
(the mean over the data positions of -sum_t p_t log p_t). A sanity counter,
with no expected direction on the harness's traffic: it has to lie between
0 (every token leaves at one depth) and ln ``total_ut_steps`` (1.386 at 4),
and on uniform random tokens, where no depth predicts better than another,
where in that range a window's median falls is a seed's trajectory (0.001
to 1.3 by seed on the chip). The step's other loop counters
(``loop_exit_step_mean``, ``loop_p_last``, ``loop_ce_<t>``) are journaled
and have no entry in the table until traffic a depth can predict gives
them something to say. None on a step that counts no such thing."""

from benchmark import readers


def read(run):
    return readers.counter_median(run, "loop_exit_entropy")
