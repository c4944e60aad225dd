"""The fused KD-KL kernel (``kernels/kd_kl``): operations and bytes of
its forward and backward calls at their true sizes.

Per row of ``classes`` logits, the forward reads the teacher's and the
student's logits and writes the row's KL and two log-sum-exps; the
backward reads both logits, the two log-sum-exps and the incoming
gradient and writes the student's gradient.  Operations: about six per
logit each way (two exponentials, the differences and products); the
bytes bound them by far.  A traced call shows its rows and classes padded
to the kernel's blocks, so the true sizes come from the cell.
"""
from __future__ import annotations

ITEM = 4


def is_forward(kernel) -> bool:
    ops = kernel.operands
    return len(ops) == 2 and all(len(o) == 2 for o in ops)


def is_backward(kernel) -> bool:
    ops = kernel.operands
    return len(ops) == 5 and [len(o) for o in ops] == [2, 2, 1, 1, 1]


def least_seconds(rows: int, classes: int, backward: bool,
                  peaks: dict) -> float:
    flops = 6.0 * rows * classes
    if backward:
        nbytes = ITEM * (3 * rows * classes + 3 * rows)
    else:
        nbytes = ITEM * (2 * rows * classes + 3 * rows)
    return max(flops / peaks["flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
