"""Flash attention's backward kernels alone: the least time the chip could
take for the backward's part of the family's ``work.kernels["flash_attn"]``
in the steps of the traced window, over the self time on the first device of
the Pallas kernels whose own name starts with ``flash_bwd``
(``kernel_names``: the fused backward, or the dQ and the dK/dV kernel, each
plain or windowed).

Every family counts 6 matmuls a pair of query and key, 2 in the forward and
4 in the backward (the backward's recomputation of the scores is not
counted), so the backward's part is 4/6 of the count.  Its bytes are scaled
alike; that changes nothing where compute bounds the count, as it does in
every cell.  With ``kernel.flash_fwd_roofline`` it gives the combined
reading: 1 / (1 / (3 fwd) + 2 / (3 bwd)).  None where no kernel carries the
name (a program from before the kernels were named)."""
import kernel_names


def read(ctx):
    return kernel_names.flash_roofline(ctx, kernel_names.FLASH_BWD, 4 / 6)
