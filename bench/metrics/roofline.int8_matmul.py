"""``int8_matmul``'s share of its roofline (``opcount.roofline_share``)."""
import opcount


def read(ctx):
    """``int8_matmul``'s roofline share, in %."""
    return opcount.roofline_share(ctx, "int8_matmul")
