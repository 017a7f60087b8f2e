"""The fused PCG update with the block-Jacobi apply: x' = x + a p,
r' = r - a q, z' = P r' (the program's ``kernels/fused_pcg``).

Required bytes: reads of x, p, r and q, writes of x', r' and z', and one
read of the symmetric block-Jacobi inverse, b (b + 1) / 2 values per b x b
block. Elementwise work is a few flops per 4-byte element, so the kernel is
bandwidth-bound and its roofline is bytes over the chip's HBM bandwidth.
"""
BOUND = "bytes"
# the custom call of the ``pallas_call`` named ``fused_pcg_update``, or,
# without a name, of the jitted ``fused_pcg_update_batched`` around it
NAME = r"^%fused_pcg_update(_batched)?(\.\d+)?$"


def required_bytes(g: dict) -> float:
    b = g["block"]
    return (7 * g["m"] + (g["m"] // b) * b * (b + 1) // 2) * g["itemsize"]
