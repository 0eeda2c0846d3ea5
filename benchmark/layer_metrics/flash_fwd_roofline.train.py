"""Kernels: the roofline's least time of one forward flash-attention call at the cell's shapes (compute-bound at seq 1024) over that kernel's mean device time in the trace."""

from benchmark import readers

# The forward kernel on the device's "XLA Ops" line, from the by-hand look at a
# trace (PR 23): the pallas_calls carry no name=, so the trace calls all three
# flash kernels %attn.<n> after the model's named scope, and the forward is the
# one whose outputs are (o bf16[B*H,L,D], lse f32[B*H,L,1]); dq has one output
# and dkv two bf16 ones. A name= on the pallas_call would make this a plain name.
FLASH_FWD_OP = r"^%attn\.\d+ = \(bf16\[[\d,]+\]\S*, f32\[[\d,]+,1\]\S*\) custom-call\("


def read(run):
    return readers.flash_fwd_roofline_pct(run, FLASH_FWD_OP)
