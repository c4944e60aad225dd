"""Model step, whole round: the model FLOPs the window's rounds require
over window seconds x chips x peak FLOP/s, in percent.

Required per round, F being one example's forward (``bench.flops``):
3 F for each trained example (forward and backward); F for each distinct
example a client trains on, under FedGKD, for the teacher's logits; F for
each test example evaluated.  Padded or untrained teacher rows do not
count."""
from bench import traffic
from bench.flops import resnet


def read(run):
    cfg = run.cfg
    f = resnet.forward_flops(cfg)
    kd = run.traffic["algorithm"].startswith("fedgkd")
    total = 0.0
    for r in run.window_rounds:
        cohort = run.cohorts[r]
        total += 3 * f * traffic.trained_examples(cfg, run.steps, run.sizes,
                                                  cohort)
        if kd:
            total += f * sum(
                min(run.sizes[int(c)],
                    run.steps * min(cfg["batch_size"], run.sizes[int(c)]))
                for c in cohort)
        total += f * cfg["test_size"]
    return 100.0 * total / (run.window_s * run.chips
                            * run.peaks["flops_per_s"])
