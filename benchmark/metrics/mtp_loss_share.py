"""The prediction module's share of the training loss: the median over the
window's steps of ``mtp_loss_coef`` x the step program's ``loss_mtp`` over
its ``loss`` (the coefficient is the configuration file's). About
coefficient / (1 + coefficient) while both heads predict nothing (0.23 at
0.3); it reads 0 if the module falls out of the step, and None on a step
that counts no ``loss_mtp`` or a configuration without the coefficient."""

import statistics


def read(run):
    coef = run["cell"].config.get("mtp_loss_coef")
    if coef is None:
        return None
    shares = [
        coef * r["counters"]["loss_mtp"] / r["loss"] for r in run["records"]
        if "loss_mtp" in r.get("counters", {})
    ]
    return statistics.median(shares) if shares else None
