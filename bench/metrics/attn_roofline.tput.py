"""The Pallas int4 paged decode kernel's share of its roofline: the
least time of the bytes its calls need (``work.attn_row_bytes``) over the
device time of its events in the traced window."""
from bench import devtrace, readers

# the kernel's HLO instruction in the device trace is named after the
# function that calls it: quant_decode_attention_paged_fwd.<n>
KERNEL = "quant_decode_attention_paged_fwd"


def read(ctx):
    return readers.attn_roofline(
        ctx, lambda name: devtrace.op_name(name).split(".")[0] == KERNEL)
