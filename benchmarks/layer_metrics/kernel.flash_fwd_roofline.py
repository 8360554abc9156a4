"""Flash attention's forward kernels alone: the least time the chip could
take for the forward's part of the family's ``work.kernels["flash_attn"]``
in the steps of the traced window, over the self time on the first device of
the Pallas kernels whose own name starts with ``flash_fwd``
(``kernel_names``: the plain and the windowed forward).

Every family counts 6 matmuls a pair of query and key, 2 in the forward and
4 in the backward (the backward's recomputation of the scores is not
counted), so the forward's part is 2/6 of the count.  Its bytes are scaled
alike; that changes nothing where compute bounds the count, as it does in
every cell.  With ``kernel.flash_bwd_roofline`` it gives the combined
reading: 1 / (1 / (3 fwd) + 2 / (3 bwd)).  None where no kernel carries the
name (a program from before the kernels were named)."""
import kernel_names


def read(ctx):
    return kernel_names.flash_roofline(ctx, kernel_names.FLASH_FWD, 2 / 6)
