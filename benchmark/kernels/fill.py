"""Operations and bytes of one call of the Arrow fill kernel
(`pbccs_tpu/ops/fwdbwd_pallas.py`, kernel function `_fill_kernel`).

One call scans `nc` template columns of `R` reads, `W` band lanes each.
Per cell: the cross-column move (2 multiplies, 1 add), the circular prefix
scan over the band (ceil(log2 W) steps of 2 multiplies and 1 add), the
column rescale (max, divide, select): 6 + 3 ceil(log2 W) float32
operations.  The bytes a call must move once: three coefficient tensors
in and one value tensor out, (nc, R, W) float32 each, the (nc, R) mask
in and log-scales out, the (R, W) seed and (R,) seed column.
"""

import math

# The call passes no `name=`, so the trace shows it under the function it
# sits in (`%branch_1_fun.3` at PR 24).  What tells it apart is its
# signature: a Pallas custom call that returns the (nc, R, W) values and
# their (nc, R, 1) log-scales.
MATCH = (r"= \(f32\[(\d+),(\d+),\d+\]\S*, f32\[\1,\2,1\]\S*\) custom-call\("
         r".*custom_call_target=\"tpu_custom_call\"")


def work_from_dims(nc: int, reads: int, width: int) -> tuple[float, float]:
    cells = nc * reads * width
    ops = cells * (6 + 3 * math.ceil(math.log2(width)))
    nbytes = 4 * (4 * cells + 2 * nc * reads + reads * width + reads)
    return float(ops), float(nbytes)


def work(call: dict) -> tuple[float, float]:
    """`call`: {"operands": [(dtype, shape)], "outputs": [(dtype, shape)]}
    as the trace gives them; the first output is the (nc, R, W) values."""
    nc, reads, width = call["outputs"][0][1]
    return work_from_dims(nc, reads, width)
