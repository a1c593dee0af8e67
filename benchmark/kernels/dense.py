"""Operations and bytes of one call of the dense mutation-scoring kernel
(`pbccs_tpu/ops/dense_score_pallas.py`, kernel function `_dense_kernel`).

One call scores every single-base mutation slot (9 a position) of `R`
reads over `P` padded template positions from the stored alpha and beta
bands.  Per read and position the kernel links W-lane alpha and beta
columns for each slot: a fused multiply-add over the band lanes and a lane
reduction, about 4 W float32 operations a slot.  The bytes a call must move
once are its operands in and its output out, as the trace gives their
shapes.
"""

from harness import roofline

# The call passes no `name=`; the trace shows it under its jitted caller,
# `dense_interior_scores_batch`, returning (R, P, 9 slots).
MATCH = (r"^%dense_interior_scores_batch[.\d]* = f32\[\d+,\d+,9\]\S* custom-call\("
         r".*custom_call_target=\"tpu_custom_call\"")
SLOTS = 9


def work_from_dims(reads: int, positions: int, width: int,
                   nbytes: float) -> tuple[float, float]:
    return float(reads * positions * SLOTS * 4 * width), float(nbytes)


def work(call: dict) -> tuple[float, float]:
    """The first operand is the (R, ..., W) alpha layout, the output is
    (R, P, slots)."""
    reads, positions = call["outputs"][0][1][0], call["outputs"][0][1][1]
    width = call["operands"][0][1][-1]
    return work_from_dims(reads, positions, width, roofline.call_bytes(call))
