"""SpMV fused with the dot product: q = A p and p·q in one pass
(the program's ``kernels/spmv``, ``spmv_dot_batched``).

Required bytes, counted from the operator and not from any storage format:
every stored nonzero once as a 4-byte value and a 4-byte int32 column
index, one read of p and one write of q. The kernel is bandwidth-bound
(2 flops per nonzero against 8 bytes), so its roofline is bytes over the
chip's HBM bandwidth.
"""
BOUND = "bytes"
# the kernel's HLO instruction in the profiler trace: the custom call that
# the jitted ``spmv_dot_batched`` lowers to (``pallas_call`` sets no name)
NAME = r"^%spmv_dot_batched(\.\d+)?$"


def required_bytes(g: dict) -> float:
    return g["nnz"] * (g["itemsize"] + 4) + 2 * g["m"] * g["itemsize"]
