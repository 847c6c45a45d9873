"""Share of a step's `torchft::ddp::push` spans that start before the
step's last `torchft::ddp::pull` span ends: the buckets that went back
to the device while later ones were still arriving, median over the
window's steps. It says whether the line of buckets engages: 0 when
every push waits for the last pull, (n - 1) / n at best with n buckets
(the last one in issue order has nothing left to hide under). A step
with no `push` is left out; a program that has no such spans gives
None."""

from benchmark import span_readers

PUSH = "torchft::ddp::push"
PULL = "torchft::ddp::pull"


def read(run):
    def value(step):
        pushes = span_readers.named(step, PUSH)
        pulls = span_readers.named(step, PULL)
        if not pushes or not pulls:
            return None
        last_pull_ends = max(p.t1 for p in pulls)
        return sum(p.t0 < last_pull_ends for p in pushes) / len(pushes)

    return span_readers.median_per_step(run, value)
