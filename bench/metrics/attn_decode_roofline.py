"""Share of its roofline the rotated-int8 decode attention kernel reached
in the traced slice: the least time its calls need, from the live
``kv_len`` of every decoding row (FLOPs or bytes over the peak), over
the kernel's device time inside the decode programs."""
from harness import costs
from harness.readings import ATTN_OP, decode_op_ns, min_time, traced_tokens


def read(run):
    s = run.sizes
    flops = nbytes = 0.0
    for plen, i in traced_tokens(run):
        if i:
            f, b = costs.attn_decode(s, plen + i)
            flops += f * s.layers
            nbytes += b * s.layers
    t = decode_op_ns(run, ATTN_OP)
    if not t or not nbytes:
        return None
    return 100.0 * min_time(flops, nbytes, run.peaks) / (t / 1e9)
