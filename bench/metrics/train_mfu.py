"""The model operations the program's ``train`` phase runs, over that
phase's seconds x the chip's bf16 peak, in percent: each round's
training (``n_trained`` lanes x ``train_iters`` steps x ``batch``
samples at the client's ``train_sample``) and the trained step's two
measurement sweeps (2 forwards per sample of every active device at
``eval_sample``), both inside the phase; costs from the configuration's
reference file (``run.costs``).  None without the phase or the costs."""


def read(run):
    if not run.rounds or not run.peaks or not run.costs:
        return None
    seconds = run.phase_total("train")
    if seconds <= 0:
        return None
    sim, c = run.sim, run.costs
    ops = sum(r["row"]["n_trained"] * sim["train_iters"] * sim["batch"]
              * c["train_sample"] + 2 * r["row"]["n_active"]
              * sim["samples_per_device"] * c["eval_sample"]
              for r in run.rounds)
    return 100.0 * ops / (seconds * run.peaks["bf16_flops"])
