"""SpMV fused with the dot product: q = A p and p·q in one pass
(the program's ``kernels/spmv``).

Required bytes: the least that any implementation reading the assembled
operator must move, whatever its storage. Every stored value that A has to
give at least once, one read of p and one write of q. For a symmetric A
that is the values on or above the diagonal (``nnz_upper``): the rest are
the same numbers again. No index or other structure bytes count, since
storage by diagonals keeps one offset per diagonal and none per value. A
matrix-free stencil, which reads no values at all, is outside this count
and would need one of its own. The kernel is bandwidth-bound (2 flops per
nonzero against at least 4 bytes), so its roofline is bytes over the chip's
HBM bandwidth.
"""
BOUND = "bytes"
# the kernel's HLO instruction in the profiler trace: the custom call that
# the ``pallas_call`` named ``spmv_dot`` lowers to, or, without a name, the
# jitted ``spmv_dot_batched`` around it. Never ``%spmv`` (no dot) alone.
NAME = r"^%spmv_dot(_batched)?(\.\d+)?$"


def required_bytes(g: dict) -> float:
    values = g["nnz_upper"] if g["symmetric"] else g["nnz"]
    return (values + 2 * g["m"]) * g["itemsize"]
