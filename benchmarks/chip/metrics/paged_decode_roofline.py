"""paged_decode_roofline: the paged decode attention kernel's share of
its roofline in the traced window: the live K/V bytes and FLOPs of every
decode token dispatched (work.paged_decode_work) over the kernel's device
seconds.

The kernel is found by its op's text in the trace: the pallas_call carries
no name there, so it is the TPU custom call whose scalar-prefetch operands
are the page table, the lengths and the layer (three int32 operands before
the query); the prefill kernel has four."""
from chipbench import readings

KERNEL = (r"custom-call\(s32\[\d+,\d+\]\S* \S+, s32\[\d+\]\S* \S+, "
          r"s32\[1\]\S* \S+, bf16.*tpu_custom_call")


def read(rec):
    return readings.kernel_roofline(rec, "decode", KERNEL)
