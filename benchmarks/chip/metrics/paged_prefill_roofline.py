"""paged_prefill_roofline: the paged prefill attention kernel's share of
its roofline in the traced window: the bytes and FLOPs the window's
prefill chunks need (work.paged_prefill_work) over the kernel's device
seconds.

The kernel is found by its op's text in the trace: the pallas_call carries
no name there, so it is the TPU custom call whose scalar-prefetch operands
are the page table, the chunk starts, the totals and the layer (four int32
operands before the query); the decode kernel has three."""
from chipbench import readings

KERNEL = (r"custom-call\(s32\[\d+,\d+\]\S* \S+, s32\[\d+\]\S* \S+, "
          r"s32\[\d+\]\S* \S+, s32\[1\]\S* \S+, bf16.*tpu_custom_call")


def read(rec):
    return readings.kernel_roofline(rec, "prefill", KERNEL)
